"""Multi-device tests (sharded gossip + mini dry-run), run in subprocesses so
XLA_FLAGS can force placeholder devices without polluting the main test
process (which must keep seeing 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@pytest.mark.slow
def test_sharded_gossip_matches_reference():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import complete_graph, screen_all, gossip_screen_params
        from repro.core.bridge import stack_flatten
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,2), ("data","model"),
                             axis_types=(AxisType.Auto,) * 2)
        M = 4
        topo = complete_graph(M, 1)
        adj = jnp.asarray(topo.adjacency)
        rng = np.random.default_rng(0)
        params = {"a": jnp.asarray(rng.normal(size=(M, 6, 8)), jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(M, 10)), jnp.float32)}
        specs = {"a": P("data", None, "model"), "b": P("data", "model")}
        sharded = {k: jax.device_put(v, NamedSharding(mesh, specs[k])) for k,v in params.items()}
        w, unflatten = stack_flatten(params)
        for rule in ["trimmed_mean", "median", "krum"]:
            ref = unflatten(screen_all(w, adj, rule=rule, b=1))
            scheds = ["all_gather", "all_to_all"] if rule != "krum" else ["all_gather"]
            for sched in scheds:
                out = gossip_screen_params(sharded, specs, mesh=mesh, node_axes="data",
                                           rule=rule, b=1, adjacency=adj, schedule=sched)
                err = max(float(jnp.max(jnp.abs(x-y))) for x,y in
                          zip(jax.tree.leaves(out), jax.tree.leaves(ref), strict=True))
                assert err < 1e-5, (rule, sched, err)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_byzantine_attack_screened():
    """Random attack rows injected on the sharded path are screened out."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import complete_graph, gossip_screen_params
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,1), ("data","model"),
                             axis_types=(AxisType.Auto,) * 2)
        M = 8
        topo = complete_graph(M, 2)
        adj = jnp.asarray(topo.adjacency)
        rng = np.random.default_rng(0)
        params = {"a": jnp.asarray(rng.random((M, 16)), jnp.float32)}
        specs = {"a": P("data", "model")}
        byz = jnp.zeros((M,), bool).at[2].set(True).at[5].set(True)
        out = gossip_screen_params(params, specs, mesh=mesh, node_axes="data",
                                   rule="trimmed_mean", b=2, adjacency=adj,
                                   schedule="all_gather", byz_mask=byz, attack="random",
                                   key=jax.random.PRNGKey(0), t=3)
        honest = np.asarray(~byz)
        y = np.asarray(out["a"])[honest]
        hv = np.asarray(params["a"])[honest]
        assert (y >= hv.min(0)-1e-4).all() and (y <= hv.max(0)+1e-4).all()
        print("OK")
    """)
    assert "OK" in out


def test_mini_multipod_dryrun_lowers():
    """2x2x2 'multi-pod' mesh analog: train step for a reduced arch lowers,
    compiles, and contains node-axis collectives."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.shapes import InputShape, train_specs
        from repro.core.graph import complete_graph
        from repro.core.bridge import replicate
        from repro.launch import sharding
        from repro.launch.steps import make_train_step
        from repro.models import api as model_api

        from jax.sharding import AxisType
        mesh = jax.make_mesh((2,2,2), ("pod","data","model"),
                             axis_types=(AxisType.Auto,) * 3)
        nax = ("pod","data")
        cfg = get_config("qwen3-4b").reduced()
        api = model_api.build(cfg)
        m = 4
        shape = InputShape("mini", 64, 8, "train")
        key = jax.random.PRNGKey(0)
        pshapes = jax.eval_shape(lambda k: replicate(api.init_params(k, cfg), m), key)
        pspecs = sharding.param_specs(cfg, pshapes, node_axes=nax)
        batch = train_specs(cfg, shape, m)
        bspecs = sharding.train_batch_specs(batch, nax)
        adj = jnp.asarray(complete_graph(m, 1).adjacency)
        step = make_train_step(cfg, mesh, nax, pspecs, adj, rule="trimmed_mean",
                               num_byzantine=1)
        in_sh = (sharding.named(mesh, pspecs), sharding.named(mesh, bspecs), None)
        with mesh:
            lowered = jax.jit(step, in_shardings=in_sh).lower(
                pshapes, batch, jax.ShapeDtypeStruct((), jnp.float32))
            compiled = lowered.compile()
        txt = compiled.as_text()
        assert "all-gather" in txt or "all-reduce" in txt
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        print("OK")
    """, devices=8)
    assert "OK" in out


def test_serve_step_lowers_with_cache_sharding():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.shapes import InputShape, decode_token_specs
        from repro.launch import sharding
        from repro.launch.steps import make_serve_step
        from repro.models import api as model_api

        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,2), ("data","model"),
                             axis_types=(AxisType.Auto,) * 2)
        nax = ("data",)
        cfg = get_config("mistral-nemo-12b").reduced()
        api = model_api.build(cfg)
        shape = InputShape("mini_decode", 256, 8, "decode")
        key = jax.random.PRNGKey(0)
        pshapes = jax.eval_shape(lambda k: api.init_params(k, cfg), key)
        pspecs = sharding.param_specs(cfg, pshapes, node_axes=None)
        cshapes = jax.eval_shape(lambda: api.init_cache(cfg, shape.global_batch, shape.seq_len))
        cspecs = sharding.cache_specs(cfg, cshapes, node_axes=nax, mesh=mesh,
                                      batch=shape.global_batch, seq_len=shape.seq_len)
        batch = decode_token_specs(cfg, shape)
        bspecs = sharding.serve_batch_specs(batch, nax, shape.global_batch, mesh)
        step = make_serve_step(cfg)
        in_sh = (sharding.named(mesh, pspecs), sharding.named(mesh, cspecs),
                 sharding.named(mesh, bspecs))
        with mesh:
            compiled = jax.jit(step, in_shardings=in_sh).lower(pshapes, cshapes, batch).compile()
        assert compiled.memory_analysis().temp_size_in_bytes >= 0
        print("OK")
    """, devices=8)
    assert "OK" in out

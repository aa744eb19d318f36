#!/usr/bin/env python3
"""Chip smoke test: drive BRIDGE's training paths once on a TPU.

    python chip_smoke.py             # one chip: phases (a)-(d), in order
    python chip_smoke.py --chips 4   # four chips: the sharded mesh path only

One chip, in one process:

(a) `repro.stream.StreamBridgeTrainer` on qwen3-4b at its published widths
    (d_model 2560, 32 q / 8 kv heads, head_dim 128, d_ff 9728, qk-norm),
    cut to 1 layer and 1/8 of the vocabulary, M = 4 nodes, driven by one
    donated `run_chunks` call (one compiled program);
(b) the flat `repro.core.BridgeTrainer` on the paper's MNIST-like linear task
    (d = 7850, M = 128, sparse small-world graph with K <= 16, trimmed_mean
    b = 2 against `alie`) for 200 ticks through `run_chunks`, scored by
    honest test accuracy;
(c) the training CLI, `repro.launch.train.main`, in process with
    ``--reduce --metrics`` (manifest and metric-writer threads included);
(d) the Pallas screening kernels compiled for the chip (``interpret=False``),
    checked against `repro.kernels.ref`.

``--chips 4`` runs `repro.launch.steps.make_train_step` /
`repro.core.gossip.gossip_screen_params` on a (4, 1) ("data", "model") mesh,
one node per chip, compares the sharded screen with single-device
`screen_all`, checks that every chip holds its own node's shard, and takes a
few training steps.

Each phase prints one line: shapes, compile seconds (lowering and backend
compile), seconds per tick (the call's wall less those, over its ticks), final
loss or accuracy, and the device's ``peak_bytes_in_use`` (the process-wide
peak so far).  They are smoke numbers from a single run, not benchmark
measurements.  The script fails (non-zero exit, no ``ok`` line) when JAX
finds no TPU, when a loss is not finite, or when any check fails; it catches
no phase's exception.  Its last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Artifacts (phase (c)'s metric stream and manifest) go to ``run_chip_smoke/``
in the checkout (gitignored).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import BridgeConfig, BridgeTrainer, erdos_renyi, replicate  # noqa: E402
from repro.core.graph import small_world  # noqa: E402
from repro.launch.cache import use_compilation_cache  # noqa: E402
from repro.models import api as model_api  # noqa: E402

OUT_DIR = os.path.join(ROOT, "run_chip_smoke")
NOTE = "(smoke run, not a benchmark)"

# phase (b): the paper task's deployment
PAPER_NODES, PAPER_TICKS, PAPER_NEAREST, PAPER_B = 128, 200, 6, 2
# Honest test accuracy after 200 ticks.  The same phase on the CPU
# (JAX_PLATFORMS=cpu: identical data, graph and seeds) reaches 0.9928.  On the
# chip only the f32 summation order of matmuls and reductions differs, which
# moves it by a few hundredths of a percent, not points.  0.98 sits 1.3
# points below the CPU value: a run that diverged, stalled or did not train
# (about 0.1) fails it by far.
PAPER_MIN_ACCURACY = 0.98


def stream_config():
    """qwen3-4b at published widths, depth cut to 1 layer and vocabulary to
    1/8: one pipeline stage of a deployment that shards the vocabulary over 8
    chips."""
    return dataclasses.replace(get_config("qwen3-4b"), num_layers=1,
                               vocab_size=151936 // 8)


class CompileClock:
    """Seconds JAX spends lowering and compiling, and how many backend
    compiles ran, while the context is open (`jax.monitoring`).  Tracing is
    left out: nested jits report nested trace spans, which would count
    twice."""

    _EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        self.seconds, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def _listen(self, event, duration, **_):
        if event in self._EVENTS:
            self.seconds += duration
            self.compiles += event.endswith("backend_compile_duration")

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


def report(phase: str, **fields) -> None:
    body = "  ".join(f"{k}={v}" for k, v in fields.items())
    print(f"phase {phase}: {body}  peak_bytes_in_use={peak_bytes()}  {NOTE}", flush=True)


def count_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


def phase_stream(cfg, *, nodes: int = 4, ticks: int = 4, seq: int = 512, batch: int = 1):
    """(a) The LLM-scale trainer through one donated `run_chunks` call."""
    from repro.data.tokens import TokenPipeline
    from repro.stream import StreamBridgeTrainer

    api = model_api.build(cfg)
    topo = erdos_renyi(nodes, 0.9, 1, seed=0)
    trainer = StreamBridgeTrainer(
        BridgeConfig(topology=topo, rule="trimmed_mean", num_byzantine=1, attack="sign_flip"),
        api.grad_fn())
    key = jax.random.PRNGKey(0)
    params = jax.jit(lambda k: replicate(api.init_params(k, cfg), nodes, perturb=0.005, key=k))(key)
    per_node = count_params(params) // nodes
    state = trainer.init(params)
    del params  # the state owns them; run_chunks donates the carry
    pipe = TokenPipeline(cfg.vocab_size, seq, batch, nodes, seed=0)
    tokens = [pipe.batch(i) for i in range(ticks)]  # host data built before the clock
    with CompileClock() as cc:
        t0 = time.perf_counter()
        state, ms = trainer.run_chunks(state, lambda i: tokens[i], ticks, chunk=ticks)
        jax.block_until_ready(state.params)
        wall = time.perf_counter() - t0
    loss = np.asarray(ms["loss"])
    require(np.isfinite(loss).all(), f"stream losses finite: {loss}")
    report("a", trainer="StreamBridgeTrainer", arch=f"{cfg.name}[layers={cfg.num_layers},"
           f"vocab={cfg.vocab_size},d_model={cfg.d_model},d_ff={cfg.d_ff}]",
           params_per_node=per_node, nodes=nodes, batch=batch, seq=seq, ticks=ticks,
           compiles=cc.compiles, compile_s=f"{cc.seconds:.1f}",
           s_per_tick=f"{(wall - cc.seconds) / ticks:.3f}",
           loss_first=f"{loss[0]:.4f}", loss_last=f"{loss[-1]:.4f}")


def phase_paper(*, nodes: int = PAPER_NODES, ticks: int = PAPER_TICKS,
                nearest: int = PAPER_NEAREST, b: int = PAPER_B,
                min_accuracy: float = PAPER_MIN_ACCURACY) -> float:
    """(b) The paper task through the flat trainer's `run_chunks`."""
    from repro.sim.tasks import linear_task

    task = linear_task(nodes, 0, partition="iid", batch=32, num_train=100 * nodes,
                       num_test=1000, seed=0)
    topo = small_world(nodes, nearest, b, rewire_prob=0.2, seed=0)
    trainer = BridgeTrainer(
        BridgeConfig(topology=topo, rule="trimmed_mean", num_byzantine=b, attack="alie",
                     sparse=True), task.grad_fn)
    state = trainer.init(task.init_fn(0), seed=0)
    batches = [task.batch_fn(i) for i in range(ticks)]  # host data built before the clock
    chunk = 50
    with CompileClock() as cc:
        t0 = time.perf_counter()
        state, ms = trainer.run_chunks(state, lambda i: batches[i], ticks, chunk=chunk)
        jax.block_until_ready(state.params)
        wall = time.perf_counter() - t0
    loss = np.asarray(ms["loss"])
    require(np.isfinite(loss).all(), "paper-task losses finite")
    acc = task.eval_accuracy(state.params, trainer.honest_mask)
    require(acc >= min_accuracy, f"honest test accuracy {acc:.4f} >= {min_accuracy}")
    report("b", trainer="BridgeTrainer", task="mnist_like_linear", d=count_params(
        task.init_fn(0)) // nodes, nodes=nodes, max_in_degree=trainer.neighbors.k,
        rule="trimmed_mean", b=b, attack="alie", ticks=ticks, chunk=chunk,
        compile_s=f"{cc.seconds:.1f}", s_per_tick=f"{(wall - cc.seconds) / ticks:.4f}",
        loss_last=f"{loss[-1]:.4f}", honest_accuracy=f"{acc:.4f}")
    return acc


def phase_cli(*, steps: int = 8):
    """(c) The training CLI in process, metric ring and writer threads on."""
    from repro.launch import train
    from repro.obs.metrics import read_metrics

    run_dir = os.path.join(OUT_DIR, "train_cli")
    shutil.rmtree(run_dir, ignore_errors=True)
    argv = ["--arch", "qwen3-4b", "--reduce", "--nodes", "6", "--byzantine", "1",
            "--attack", "random", "--steps", str(steps), "--batch", "2", "--seq", "32",
            "--metrics", run_dir, "--metrics-capacity", str(steps // 2)]
    with CompileClock() as cc:
        t0 = time.perf_counter()
        train.main(argv)
        wall = time.perf_counter() - t0
    rows = read_metrics(os.path.join(run_dir, "metrics.jsonl"))
    losses = np.asarray([r["loss"] for r in rows], np.float64)
    require(len(rows) == steps, f"{steps} metric rows streamed, got {len(rows)}")
    require(np.isfinite(losses).all(), "CLI losses finite")
    with open(os.path.join(run_dir, "manifest.json")) as f:
        manifest = json.load(f)
    require(manifest.get("ended") is True, "run manifest closed")
    report("c", entry="repro.launch.train.main", argv=" ".join(argv[:12]), steps=steps,
           compile_s=f"{cc.seconds:.1f}", s_per_tick=f"{(wall - cc.seconds) / steps:.3f}",
           loss_last=f"{losses[-1]:.4f}", metric_rows=len(rows))


def phase_kernels(*, n: int = 17, d: int = 7850, b: int = 2, nodes: int = 128,
                  nearest: int = 6):
    """(d) The Pallas screening kernels, compiled for the chip, against the
    pure-jnp oracles at the CPU tests' tolerances (the median selects a value
    and must match bit for bit; the trimmed mean sums its survivors in
    another order)."""
    from repro.core.neighbors import NeighborTable
    from repro.kernels import ref
    from repro.kernels.gather_screen import gather_screen_pallas
    from repro.kernels.median import median_pallas
    from repro.kernels.trimmed_mean import trimmed_mean_pallas

    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    mask = jnp.asarray(rng.random(n) < 0.8).at[: 2 * b + 1].set(True)
    sv = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    nbr = NeighborTable.from_adjacency(small_world(nodes, nearest, b, seed=0).adjacency)
    w = jnp.asarray(rng.normal(size=(nodes, d)), jnp.float32)
    cases = {
        "trimmed_mean": (lambda: trimmed_mean_pallas(v, mask, sv, b, interpret=False),
                         lambda: ref.trimmed_mean_ref(v, mask, sv, b), 1e-5),
        "median": (lambda: median_pallas(v, mask, interpret=False),
                   lambda: ref.median_ref(v, mask), 0.0),
        "gather_screen": (
            lambda: gather_screen_pallas(w, jnp.asarray(nbr.idx), nbr.valid_dev, w, b,
                                         interpret=False),
            lambda: ref.trimmed_mean_ref(jnp.take(w, nbr.safe_idx, axis=0), nbr.valid_dev, w, b),
            1e-5),
    }
    timings = []
    for name, (kernel, oracle, tol) in cases.items():
        with CompileClock() as cc:
            out = jax.block_until_ready(kernel())
        t0 = time.perf_counter()
        jax.block_until_ready(kernel())
        us = (time.perf_counter() - t0) * 1e6
        exp = np.asarray(oracle())
        if tol:
            np.testing.assert_allclose(np.asarray(out), exp, rtol=tol, atol=tol, err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(out), exp, err_msg=name)
        timings.append(f"{name}:compile_s={cc.seconds:.2f},us={us:.0f}")
    report("d", kernels=";".join(timings), n=n, d=d, b=b, gather_nodes=nodes,
           gather_k=nbr.k, matches_ref=True)


def require_node_per_device(params, nodes: int) -> None:
    """Every device holds exactly one node's slice of every leaf, and each
    node sits on its own device (code that only ever ran on virtual CPU
    devices can leave everything on device 0)."""
    for leaf in jax.tree_util.tree_leaves(params):
        shards = leaf.addressable_shards
        rows = sorted(s.index[0].start or 0 for s in shards)
        require(rows == list(range(nodes)) and len({s.device for s in shards}) == nodes
                and all(s.data.shape[0] == 1 for s in shards),
                f"leaf {leaf.shape}: one node per device, got rows {rows} on "
                f"{[s.device.id for s in shards]}")


def phase_mesh(cfg, *, steps: int = 3, seq: int = 512, batch: int = 1):
    """(--chips 4) The sharded mesh path: one node per chip on a (4, 1)
    ("data", "model") mesh; gossip_screen_params against single-device
    screen_all, shard placement, then a few make_train_step steps."""
    from jax.sharding import AxisType

    from repro.core.gossip import gossip_screen_params
    from repro.core.screening import screen_all
    from repro.data.tokens import TokenPipeline
    from repro.launch import sharding
    from repro.launch.steps import make_train_step

    devs = jax.devices()
    nodes = len(devs)
    mesh = jax.make_mesh((nodes, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    nax = ("data",)
    api = model_api.build(cfg)
    key = jax.random.PRNGKey(0)
    init = lambda k: replicate(api.init_params(k, cfg), nodes, perturb=0.005, key=k)
    pspecs = sharding.param_specs(cfg, jax.eval_shape(init, key), node_axes=nax)
    shardings = sharding.named(mesh, pspecs)
    params = jax.jit(init, out_shardings=shardings)(key)
    adj = jnp.asarray(erdos_renyi(nodes, 0.9, 1, seed=0).adjacency)

    require_node_per_device(params, nodes)

    with CompileClock() as cc_g:
        t0 = time.perf_counter()
        screened = jax.jit(lambda p: gossip_screen_params(
            p, pspecs, mesh=mesh, node_axes=nax, rule="trimmed_mean", b=1,
            adjacency=adj, schedule="all_gather"), out_shardings=shardings)(params)
        jax.block_until_ready(screened)
        wall_g = time.perf_counter() - t0
    ref_screen = jax.jit(lambda w: screen_all(w, adj, rule="trimmed_mean", b=1, chunk=1 << 20))
    err = 0.0
    for got, leaf in zip(jax.tree_util.tree_leaves(screened), jax.tree_util.tree_leaves(params),
                         strict=True):
        want = ref_screen(jax.device_put(leaf, devs[0]).reshape(nodes, -1).astype(jnp.float32))
        got1 = jax.device_put(got, devs[0]).reshape(nodes, -1)
        err = max(err, float(jnp.max(jnp.abs(got1 - want))))
        del want, got1  # one leaf's copies at a time on device 0
    require(err < 1e-5, f"sharded screen matches screen_all (max abs err {err:.3g})")

    step = jax.jit(make_train_step(cfg, mesh, nax, pspecs, adj, rule="trimmed_mean",
                                   num_byzantine=1),
                   in_shardings=(shardings, None, None), out_shardings=(shardings, None),
                   donate_argnums=(0,))
    pipe = TokenPipeline(cfg.vocab_size, seq, batch, nodes, seed=0)
    bsh = sharding.named(mesh, sharding.train_batch_specs(pipe.batch(0), nax))
    batches = [jax.device_put(pipe.batch(i), bsh) for i in range(steps)]
    losses = []
    with CompileClock() as cc_s:
        t0 = time.perf_counter()
        for i in range(steps):
            params, mets = step(params, batches[i], jnp.float32(i))
            losses.append(mets["loss"])
        jax.block_until_ready(params)
        wall_s = time.perf_counter() - t0
    losses = np.asarray(jax.device_get(losses))
    require(np.isfinite(losses).all(), f"mesh train losses finite: {losses}")
    require_node_per_device(params, nodes)
    report("mesh", path="make_train_step+gossip_screen_params", mesh=f"({nodes},1)",
           arch=f"{cfg.name}[layers={cfg.num_layers},vocab={cfg.vocab_size}]",
           params_per_node=count_params(params) // nodes, schedule="all_gather",
           screen_max_abs_err=f"{err:.3g}", screen_compile_s=f"{cc_g.seconds:.1f}",
           screen_s=f"{wall_g - cc_g.seconds:.3f}", steps=steps,
           compile_s=f"{cc_s.seconds:.1f}", s_per_tick=f"{(wall_s - cc_s.seconds) / steps:.3f}",
           loss_first=f"{losses[0]:.4f}", loss_last=f"{losses[-1]:.4f}",
           node_per_device=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: phases (a)-(d) on one chip; 4: the sharded mesh path only")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); nothing ran",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} device(s)",
              file=sys.stderr)
        return 1
    cache = use_compilation_cache()
    os.makedirs(OUT_DIR, exist_ok=True)
    cfg = stream_config()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}  compile cache: {cache}")
    print(f"config: {cfg.name} at published widths (d_model={cfg.d_model}, heads="
          f"{cfg.num_heads}/{cfg.num_kv_heads}, head_dim={cfg.hd}, d_ff={cfg.d_ff}, "
          f"qk_norm={cfg.qk_norm}); cut to num_layers={cfg.num_layers} and vocab_size="
          f"{cfg.vocab_size} (151936 // 8): one pipeline stage of a deployment that "
          f"shards the vocabulary over 8 chips", flush=True)
    if args.chips == 4:
        phase_mesh(cfg)
    else:
        phase_stream(cfg)
        phase_paper()
        phase_cli()
        phase_kernels()
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

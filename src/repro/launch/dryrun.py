"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against ShapeDtypeStruct stand-ins (no allocation), print
memory/cost analysis, and derive the per-chip roofline terms with the
loop-aware HLO analyzer.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-3b \
        --shape train_4k [--multi-pod] [--gossip all_gather] [--remat] \
        [--json experiments/dryrun]

One (arch, shape, mesh) per invocation — the sweep script
(launch/sweep.py) fans out subprocesses and aggregates the table.
"""
# The VERY FIRST jax-visible action: add 512 placeholder devices to whatever
# XLA_FLAGS the environment set, BEFORE any other import (jax locks the
# device count on first backend init).
import os  # noqa: E402
os.environ["XLA_FLAGS"] = " ".join(
    filter(None, [os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512"]))

import argparse
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config
from repro.configs.shapes import (
    decode_token_specs,
    prefill_specs,
    shape_applicable,
    train_specs,
)
from repro.core.graph import erdos_renyi
from repro.launch import hlo_analysis, sharding
from repro.launch.mesh import make_production_mesh, node_axes, num_nodes
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models import api as model_api


def _tree_sds(tree):
    return jax.tree_util.tree_map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def model_flops_per_chip(cfg, shape, kind: str, n_chips: int, n_nodes: int) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N*D inference (active params for
    MoE), per chip."""
    n_total = model_api.param_count(cfg)
    n_active = n_total
    if cfg.num_experts:
        ff_mult = 3  # swiglu experts
        n_moe_layers = cfg.num_layers - cfg.first_dense_layers
        routed = ff_mult * cfg.d_model * cfg.moe_d_ff * cfg.num_experts * n_moe_layers
        n_active = n_total - routed + routed * cfg.top_k / cfg.num_experts
    if kind == "train":
        # global_batch is split across BRIDGE nodes; total trained tokens per
        # step is global_batch*seq regardless of M.
        tokens = shape.global_batch * shape.seq_len
        per_model = 6.0 * n_active * tokens
    elif kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        per_model = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        per_model = 2.0 * n_active * shape.global_batch
    return per_model / n_chips


def build_lowerable(cfg, shape, mesh, args):
    """Returns (fn, example_args, in_shardings) ready for jit().lower()."""
    nax = node_axes(mesh)
    kind = shape.kind
    key = jax.random.PRNGKey(0)
    api = model_api.build(cfg)

    if kind == "train":
        m = num_nodes(mesh)
        from repro.core.bridge import replicate

        pshapes = jax.eval_shape(lambda k: replicate(api.init_params(k, cfg), m), key)
        pspecs = sharding.param_specs(cfg, pshapes, node_axes=nax, layout=args.layout)
        # gossip always exchanges model-sharded coordinate shards (each chip
        # screens distinct coordinates even under the dp layout)
        gspecs = (pspecs if args.layout == "tp"
                  else sharding.param_specs(cfg, pshapes, node_axes=nax, layout="tp"))
        batch = train_specs(cfg, shape, m)
        bspecs = sharding.train_batch_specs(batch, nax, layout=args.layout)
        topo = None
        for p in (0.6, 0.7, 0.8, 0.9):
            try:
                topo = erdos_renyi(m, p, args.byzantine, seed=0)
                break
            except RuntimeError:
                continue
        assert topo is not None, "could not build Assumption-4 graph"
        adjacency = jnp.asarray(topo.adjacency)
        step = make_train_step(
            cfg, mesh, nax, gspecs, adjacency,
            rule=args.rule, num_byzantine=args.byzantine,
            gossip_schedule=args.gossip, gossip_first=not args.no_overlap,
            gossip_quantize=args.gossip_quant,
        )
        in_sh = (sharding.named(mesh, pspecs), sharding.named(mesh, bspecs), None)
        ex = (pshapes, batch, jax.ShapeDtypeStruct((), jnp.float32))
        return step, ex, in_sh

    if kind == "prefill":
        pshapes = jax.eval_shape(lambda k: api.init_params(k, cfg), key)
        pspecs = sharding.param_specs(cfg, pshapes, node_axes=None)
        batch = prefill_specs(cfg, shape)
        bspecs = sharding.serve_batch_specs(batch, nax, shape.global_batch, mesh)
        step = make_prefill_step(cfg)
        in_sh = (sharding.named(mesh, pspecs), sharding.named(mesh, bspecs))
        return step, (pshapes, batch), in_sh

    # decode
    pshapes = jax.eval_shape(lambda k: api.init_params(k, cfg), key)
    pspecs = sharding.param_specs(cfg, pshapes, node_axes=None)
    b = shape.global_batch
    if cfg.family == "encdec":
        cshapes = jax.eval_shape(lambda: api.init_cache(cfg, b, shape.seq_len))
    else:
        cshapes = jax.eval_shape(lambda: api.init_cache(cfg, b, shape.seq_len))
    cspecs = sharding.cache_specs(cfg, cshapes, node_axes=nax, mesh=mesh,
                                  batch=b, seq_len=shape.seq_len)
    batch = decode_token_specs(cfg, shape)
    bspecs = sharding.serve_batch_specs(batch, nax, b, mesh)
    step = make_serve_step(cfg)
    in_sh = (sharding.named(mesh, pspecs), sharding.named(mesh, cspecs),
             sharding.named(mesh, bspecs))
    return step, (pshapes, cshapes, batch), in_sh


def run_one(arch: str, shape_name: str, multi_pod: bool, args) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = math.prod(mesh.shape.values())
    shape = SHAPES[shape_name]
    cfg = get_config(arch, dtype=args.dtype, remat=args.remat)
    ok, why = shape_applicable(cfg, shape)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "gossip": args.gossip if shape.kind == "train" else None,
        "rule": args.rule if shape.kind == "train" else None,
        "remat": args.remat,
        "layout": args.layout,
        "gossip_quant": args.gossip_quant,
    }
    if not ok:
        result.update(status="skipped", reason=why)
        print(json.dumps(result, indent=2))
        return result

    t0 = time.time()
    fn, ex, in_sh = build_lowerable(cfg, shape, mesh, args)
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*ex)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    cost = hlo_analysis.analyze(compiled.as_text())
    rl = hlo_analysis.roofline_from_cost(cost)
    n_nodes_ = num_nodes(mesh)
    mflops = model_flops_per_chip(cfg, shape, shape.kind, n_chips, n_nodes_)

    result.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        # memory analysis (per device)
        mem_argument_bytes=getattr(mem, "argument_size_in_bytes", None),
        mem_output_bytes=getattr(mem, "output_size_in_bytes", None),
        mem_temp_bytes=getattr(mem, "temp_size_in_bytes", None),
        mem_peak_gb=round(
            (getattr(mem, "argument_size_in_bytes", 0)
             + getattr(mem, "temp_size_in_bytes", 0)) / 1e9, 3),
        # built-in (loop-UNAWARE) numbers for reference
        xla_cost_flops=ca.get("flops"),
        # loop-aware per-chip totals
        hlo_flops=cost.flops,
        hlo_bytes=cost.bytes,
        collective_bytes=cost.coll,
        collective_wire_bytes=cost.coll_wire,
        # roofline terms (seconds, per chip per step)
        compute_s=rl.compute_s,
        memory_s=rl.memory_s,
        collective_s=rl.collective_s,
        dominant=rl.dominant,
        step_time_s=rl.step_time_s,
        model_flops_per_chip=mflops,
        useful_flops_ratio=round(mflops / cost.flops, 4) if cost.flops else None,
    )
    print(json.dumps(result, indent=2))
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--gossip", default="all_gather", choices=["all_gather", "all_to_all"])
    ap.add_argument("--rule", default="trimmed_mean")
    ap.add_argument("--byzantine", type=int, default=2)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--no-overlap", action="store_true",
                    help="issue gossip after backward (no compute overlap)")
    ap.add_argument("--gossip-quant", action="store_true",
                    help="int8-quantized gossip payloads (beyond-paper)")
    ap.add_argument("--layout", default="tp", choices=["tp", "dp"],
                    help="within-node parallelism: tensor (tp) or data (dp)")
    ap.add_argument("--json", default=None, help="directory to write result json")
    args = ap.parse_args(argv)

    result = run_one(args.arch, args.shape, args.multi_pod, args)
    if args.json:
        os.makedirs(args.json, exist_ok=True)
        tag = f"{args.arch}_{args.shape}_{result['mesh']}"
        if args.gossip != "all_gather":
            tag += f"_{args.gossip}"
        if args.remat:
            tag += "_remat"
        if args.no_overlap:
            tag += "_nooverlap"
        if args.gossip_quant:
            tag += "_quant"
        if args.layout != "tp":
            tag += f"_{args.layout}"
        with open(os.path.join(args.json, tag + ".json"), "w") as f:
            json.dump(result, f, indent=2)
    return 0 if result["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())

"""Fan out experiment matrices — batched in one compiled program, or as
subprocesses.  All modes are resumable (existing results are skipped).

* ``--mode grid`` — the rule x attack x b x seed (x network scenario) matrix
  through the batched grid engine (`repro.sim`): every pending cell runs
  inside ONE jitted vmapped ``lax.scan`` on the paper's MNIST-like linear
  task — no per-cell subprocess, retrace, or recompile.  Per-cell JSONs land
  in the result store exactly like the subprocess modes, so interrupted
  sweeps resume at the missing cells:

    PYTHONPATH=src python -m repro.launch.sweep --mode grid \
        --out experiments/grid [--rules trimmed_mean,median] \
        [--attacks random,alie] [--byz 1,2] [--seeds 0,1,2,3] \
        [--scenarios sync | ideal,lossy,...] [--codecs identity,int8,...] \
        [--grid-chunk 16]

* ``--mode dryrun`` (default) — the arch x shape x mesh lowering matrix as
  subprocesses:

    PYTHONPATH=src python -m repro.launch.sweep --out experiments/dryrun \
        [--jobs 4] [--archs a,b] [--shapes s1,s2] [--single-pod-only]

* ``--mode net`` — the legacy subprocess path for the scenario matrix via
  `repro.launch.train --net` (full training CLI per cell; prefer ``grid``
  for paper-scale sweeps):

    PYTHONPATH=src python -m repro.launch.sweep --mode net \
        --out experiments/net [--rules trimmed_mean,median] \
        [--attacks random,alie,selective_victim] [--scenarios ideal,lossy]

* ``--mode breakdown`` — breakdown-point certification (`repro.adversary`):
  binary-search / ladder the largest tolerated b per (rule, adversary) with
  batched probe rounds, writing ``BENCH_breakdown.json``-shaped output:

    PYTHONPATH=src python -m repro.launch.sweep --mode breakdown \
        --out experiments/breakdown [--rules trimmed_mean,median] \
        [--adversaries random,alie,ipm,inner_max] [--breakdown-mode ladder]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ARCHS = [
    "starcoder2-3b", "zamba2-1.2b", "qwen3-4b", "whisper-medium",
    "qwen2-vl-2b", "rwkv6-3b", "mistral-nemo-12b", "deepseek-v2-236b",
    "deepseek-v3-671b", "gemma3-12b",
]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def tag_for(arch, shape, multi_pod, extra=""):
    mesh = "2x16x16" if multi_pod else "16x16"
    return f"{arch}_{shape}_{mesh}{extra}"


def run_job(arch, shape, multi_pod, out_dir, timeout, extra_args=()):
    tag = tag_for(arch, shape, multi_pod, "".join(f"_{a.lstrip('-').replace('-','_')}" for a in extra_args if not a.startswith("--json")))
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path):
        return tag, "cached"
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", arch, "--shape", shape, "--json", out_dir,
    ]
    if shape == "train_4k":
        cmd.append("--remat")
    if multi_pod:
        cmd.append("--multi-pod")
    cmd.extend(extra_args)
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout)
        if proc.returncode != 0:
            fail = {"arch": arch, "shape": shape,
                    "mesh": "2x16x16" if multi_pod else "16x16",
                    "status": "failed", "stderr": proc.stderr[-3000:]}
            with open(path, "w") as f:
                json.dump(fail, f, indent=2)
            return tag, f"FAILED ({time.time()-t0:.0f}s)"
        return tag, f"ok ({time.time()-t0:.0f}s)"
    except subprocess.TimeoutExpired:
        fail = {"arch": arch, "shape": shape,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "timeout"}
        with open(path, "w") as f:
            json.dump(fail, f, indent=2)
        return tag, "TIMEOUT"


# Network-condition axis of the scenario matrix (--mode net); each maps to
# repro.launch.train --net flags.
NET_SCENARIOS = {
    "ideal": ["--net"],
    "lossy": ["--net", "--net-drop", "0.2"],
    "laggy": ["--net", "--net-latency", "3"],
    "lossy_laggy": ["--net", "--net-drop", "0.2", "--net-latency", "3"],
    "bandwidth64": ["--net", "--net-cap", "64"],
    "churn": ["--net", "--net-schedule", "churn", "--net-churn-prob", "0.3"],
    "partition": ["--net", "--net-schedule", "partition"],
}


def run_net_job(rule, attack, scenario, out_dir, timeout, arch, steps):
    tag = f"net_{rule}_{attack}_{scenario}"
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path):
        return tag, "cached"
    cmd = [
        sys.executable, "-m", "repro.launch.train",
        "--arch", arch, "--reduce", "--nodes", "6", "--byzantine", "1",
        "--rule", rule, "--attack", attack, "--steps", str(steps),
        "--batch", "2", "--seq", "32", "--log-every", str(steps),
    ] + NET_SCENARIOS[scenario]
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout)
        status = "ok" if proc.returncode == 0 else "failed"
        with open(path, "w") as f:
            json.dump({"rule": rule, "attack": attack, "scenario": scenario,
                       "status": status, "stdout": proc.stdout[-3000:],
                       "stderr": proc.stderr[-3000:] if status == "failed" else ""},
                      f, indent=2)
        return tag, f"{status.upper() if status != 'ok' else status} ({time.time()-t0:.0f}s)"
    except subprocess.TimeoutExpired:
        with open(path, "w") as f:
            json.dump({"rule": rule, "attack": attack, "scenario": scenario,
                       "status": "timeout"}, f, indent=2)
        return tag, "TIMEOUT"


def run_grid_mode(args) -> None:
    """One-compile batched sweep over rule x attack x b x seed (x scenario) on
    the paper's MNIST-like linear task, resuming from the per-cell store."""
    import jax
    import jax.numpy as jnp

    from repro.core import replicate
    from repro.data import make_mnist_like, partition_iid
    from repro.data.partition import stack_node_batches
    from repro.models import small
    from repro.sim import ExperimentGrid, GridEngine, default_topology
    from repro.sim import results as results_lib
    from repro.sim.engine import stack_batches

    rules = args.rules.split(",")
    attacks = args.attacks.split(",")
    byz = [int(x) for x in args.byz.split(",")]
    seeds = [int(x) for x in args.seeds.split(",")]
    codecs = args.codecs.split(",")
    adversaries = args.adversaries.split(",") if args.adversaries else ["none"]
    scenarios = None
    if args.scenarios not in ("sync", "none", ""):
        scenarios = args.scenarios.split(",")
    m, ticks = args.grid_nodes, args.grid_ticks
    topo = default_topology(m, rules, byz, seed=0)
    grid = ExperimentGrid(topo, rules, attacks, byz, seeds, scenarios=scenarios,
                          codecs=codecs, adversaries=adversaries, lam=1.0, t0=30.0)
    done = results_lib.existing_tags(args.out)
    pending = [c for c in grid.cells() if c.tag not in done]
    print(f"{grid.num_cells} grid cells ({len(done & {c.tag for c in grid.cells()})} cached) "
          f"-> {args.out}")
    if not pending:
        return

    def grad_fn(params, batch):
        return jax.value_and_grad(lambda p: small.linear_loss(p, batch))(params)

    x, y, xt, yt = make_mnist_like(args.grid_train, args.grid_test, seed=0)
    shards = partition_iid(x, y, m, seed=0)
    batch_fn = stack_node_batches(shards, args.grid_batch, seed=0)
    batches = stack_batches(lambda i: jax.tree_util.tree_map(jnp.asarray, batch_fn(i)), ticks)

    def init_fn(seed):
        key = jax.random.PRNGKey(seed)
        return replicate(small.init_linear(key), m, perturb=0.01, key=key)

    trace_spec, events = None, None
    run_dir = args.trace or args.metrics
    if run_dir is not None:
        from repro.obs import EventLog, write_manifest

        os.makedirs(run_dir, exist_ok=True)
        write_manifest(run_dir, kind="sweep-grid", config=vars(args))
        events = EventLog(os.path.join(run_dir, "events.jsonl"))
    if args.trace is not None:
        from repro.obs import TraceSpec
        from repro.obs import trace as obs_trace

        trace_spec = TraceSpec()
    metric_spec, mwriter = None, None
    if args.metrics is not None:
        from repro.obs import AlertRules, MetricSpec, MetricWriter

        metric_spec = MetricSpec(capacity=args.metrics_capacity)
        mwriter = MetricWriter(os.path.join(args.metrics, "metrics.jsonl"),
                               alerts=AlertRules(), events=events)
    if args.profile is not None:
        os.makedirs(args.profile, exist_ok=True)
        jax.profiler.start_trace(args.profile)
    engine = GridEngine(grid, grad_fn, cells=pending,
                        num_ticks=ticks if scenarios else None, sparse=args.sparse,
                        trace=trace_spec, trust=_trust_spec(args),
                        metrics=metric_spec, events=events)
    t0 = time.time()
    state = engine.init(init_fn)
    state, metrics = engine.run(state, batches, chunk=args.grid_chunk,
                                metric_writer=mwriter)
    jax.block_until_ready(state.params)
    wall = time.time() - t0
    if mwriter is not None:
        mwriter.close()
        print(f"metric stream -> {os.path.join(args.metrics, 'metrics.jsonl')}  "
              f"(watch: python -m repro.obs.monitor {args.metrics})")
    if args.profile is not None:
        jax.profiler.stop_trace()
        if events is not None:
            events.emit("profile.capture", dir=args.profile)
        print(f"profiler trace -> {args.profile}")
    result = results_lib.collect(pending, metrics, meta={
        "num_nodes": m, "ticks": ticks, "wall_s": wall,
        "cells_per_sec": len(pending) / wall, "us_per_cell": wall / len(pending) * 1e6,
        "trace_count": engine.trace_count, "chunk": args.grid_chunk,
        "rules": engine.rule_bank, "attacks": engine.attack_bank,
        "scenarios": engine.scenario_bank, "codecs": engine.codec_bank,
        "adversaries": engine.adversary_bank,
    })
    # per-cell honest test accuracy (the paper's metric), evaluated host-side
    xt, yt = jnp.asarray(xt), jnp.asarray(yt)
    for i, rec in enumerate(result.cells):
        hm = ~engine.byz_masks[i]
        accs = [
            float(small.linear_accuracy(
                jax.tree_util.tree_map(lambda leaf: leaf[i, j], state.params), xt, yt))
            for j in hm.nonzero()[0]
        ]
        rec["accuracy"] = float(sum(accs) / max(len(accs), 1))
    if events is not None:
        events.close()
    if run_dir is not None:
        from repro.obs import write_manifest

        write_manifest(run_dir, extra={"ended": True, "wall_s": wall,
                                       "cells": len(pending)})
    if trace_spec is not None:
        senders = engine.sender_grid()
        cells_out = []
        for i, c in enumerate(pending):
            obs_i = jax.tree_util.tree_map(lambda leaf: leaf[i], state.obs)
            rec = {"tag": c.tag, "rule": c.rule,
                   **obs_trace.summarize(trace_spec, obs_i,
                                         byz_mask=engine.byz_masks[i], senders=senders)}
            cells_out.append(rec)
        summary_path = os.path.join(args.trace, "obs_summary.json")
        with open(summary_path, "w") as f:
            json.dump({"meta": {"mode": "grid", "num_nodes": m, "ticks": ticks},
                       "cells": cells_out}, f, indent=2, sort_keys=True)
        print(f"obs summary -> {summary_path}  "
              f"(render: python -m repro.obs.report {args.trace})")
    result.save_cells(args.out)
    # the aggregate covers the WHOLE store (earlier runs' cells included),
    # so a resumed sweep never truncates GridResult.json to the tail run
    full = results_lib.load_cell_store(args.out)
    full.meta.update(result.meta)
    full.meta["computed_this_run"] = len(pending)
    full.save(os.path.join(args.out, "GridResult.json"))
    print(f"{len(pending)} cells in {wall:.1f}s "
          f"({result.meta['cells_per_sec']:.2f} cells/s, "
          f"{engine.trace_count} compilation(s))")
    for rec, row in zip(result.cells, result.rows(), strict=True):
        print(f"  {row[0]:60s} acc={rec['accuracy']:.4f} loss={rec['final_loss']:.4f}")


def _fanout_workers(jobs: int) -> int:
    """Concurrent children for the subprocess modes: ``--jobs`` only when JAX
    is held to the CPU.  An accelerator belongs to one process at a time, so
    anywhere else the children run one after another."""
    return jobs if os.environ.get("JAX_PLATFORMS") == "cpu" else 1


def _trust_spec(args):
    """The `repro.trust.TrustSpec` the --trust flags describe (None when
    --trust is off — the trust-free program, bit-identical to PR 6)."""
    if not args.trust:
        return None
    from repro.trust import TrustSpec

    return TrustSpec(evict_threshold=args.trust_evict, warmup=args.trust_warmup)


def run_breakdown_mode(args) -> None:
    """Breakdown-point certification on the paper's MNIST-like linear task
    (extreme non-iid partition — consensus is *required* for honest test
    accuracy, which is what adaptive adversaries break)."""
    from repro.adversary.breakdown import BreakdownConfig, BreakdownEngine
    from repro.sim import default_topology
    from repro.sim.tasks import linear_task

    rules = args.rules.split(",")
    adversaries = (args.adversaries or "random,alie,ipm,inner_max").split(",")
    m, ticks = args.grid_nodes, args.grid_ticks
    # the topology must admit the whole probed ladder, not just b=1
    if args.trust:
        # echo quorums need gossip triangles: witnesses of a sender must be
        # adjacent to the receiver, so trust runs get the complete graph
        from repro.core import complete_graph

        topo = complete_graph(m, max(args.breakdown_b_max, 1))
    else:
        topo = default_topology(m, rules, [max(args.breakdown_b_max, 1)], seed=0)
    task = linear_task(m, ticks, batch=args.grid_batch,
                       num_train=args.grid_train, num_test=args.grid_test, seed=0)
    events = None
    if args.trace is not None:
        from repro.obs import EventLog

        os.makedirs(args.trace, exist_ok=True)
        events = EventLog(os.path.join(args.trace, "events.jsonl"))
    engine = BreakdownEngine(
        topo, rules, adversaries, task.grad_fn, task.init_fn, task.batches,
        lam=1.0, t0=30.0,
        config=BreakdownConfig(mode=args.breakdown_mode,
                               seeds=tuple(int(s) for s in args.seeds.split(",")),
                               b_max=args.breakdown_b_max,
                               loss_ratio=args.breakdown_loss_ratio,
                               score_drop=args.breakdown_score_drop),
        eval_fn=task.eval_accuracy, engine_chunk=args.grid_chunk,
        trust=_trust_spec(args), scenario=args.breakdown_scenario, events=events)
    result = engine.run()
    if events is not None:
        events.close()
    path = os.path.join(args.out, "BENCH_breakdown.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"breakdown certification ({result['meta']['cells_run']} cells, "
          f"{result['meta']['compiles']} compiles, "
          f"{result['meta']['wall_s']:.1f}s) -> {path}")
    for rule, rrec in result["rules"].items():
        stars = "  ".join(f"{a}:b*={arec['bstar']}"
                          for a, arec in rrec["adversaries"].items())
        print(f"  {rule:14s} feasible_b={rrec['feasible_b']}  {stars}  "
              f"worst={rrec['bstar_worst_adversary']}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="dryrun",
                    choices=["dryrun", "net", "grid", "breakdown"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--jobs", type=int, default=4,
                    help="concurrent subprocess jobs (dryrun/net modes); "
                         "honoured only under JAX_PLATFORMS=cpu, else 1")
    ap.add_argument("--timeout", type=int, default=1500)
    ap.add_argument("--archs", default=None)
    ap.add_argument("--shapes", default=None)
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--rules", default="trimmed_mean,median")
    # None sentinels: the per-mode defaults differ (net sweeps every scenario,
    # grid defaults to the broadcast path) and an explicitly-passed value must
    # never be second-guessed
    ap.add_argument("--attacks", default=None,
                    help="default: random,alie,selective_victim (net) / random,alie (sync grid)")
    ap.add_argument("--scenarios", default=None,
                    help=f"default: all of {','.join(NET_SCENARIOS)} (net) / sync (grid)")
    ap.add_argument("--net-arch", default="qwen3-4b")
    ap.add_argument("--net-steps", type=int, default=30)
    # --mode grid knobs (batched engine on the MNIST-like linear task)
    ap.add_argument("--byz", default="1", help="comma-separated Byzantine counts (grid mode)")
    ap.add_argument("--seeds", default="0", help="comma-separated seeds (grid mode)")
    ap.add_argument("--codecs", default="identity",
                    help="comma-separated wire codecs (repro.comm) — a grid "
                         "axis like rules/attacks (grid mode)")
    ap.add_argument("--adversaries", default=None,
                    help="comma-separated repro.adversary names — a grid axis "
                         "(grid mode; default none) and the certified attack "
                         "suite (breakdown mode; default "
                         "random,alie,ipm,inner_max)")
    # --mode breakdown knobs (repro.adversary.breakdown)
    ap.add_argument("--breakdown-mode", default="ladder", choices=["ladder", "bisect"])
    ap.add_argument("--breakdown-b-max", type=int, default=3,
                    help="deepest Byzantine count probed (topology is built "
                         "dense enough to admit it)")
    ap.add_argument("--breakdown-loss-ratio", type=float, default=4.0,
                    help="diverged when final honest loss exceeds this "
                         "multiple of the faultless reference")
    ap.add_argument("--breakdown-score-drop", type=float, default=0.15,
                    help="diverged when honest test accuracy drops this far "
                         "below the faultless reference")
    ap.add_argument("--breakdown-scenario", default=None,
                    help="run breakdown probes through the net runtime on this "
                         "repro.net scenario (e.g. ideal) — required for "
                         "equivocators, whose lies only exist per message")
    ap.add_argument("--grid-nodes", type=int, default=12)
    ap.add_argument("--grid-ticks", type=int, default=60)
    ap.add_argument("--grid-batch", type=int, default=32)
    ap.add_argument("--grid-train", type=int, default=2000)
    ap.add_argument("--grid-test", type=int, default=400)
    ap.add_argument("--grid-chunk", type=int, default=None,
                    help="max experiments per compiled call (memory bound); "
                         "default runs the whole grid in one call")
    ap.add_argument("--sparse", action="store_true",
                    help="neighbor-indexed [M, K] state layout "
                         "(repro.core.neighbors) — bit-identical to dense, "
                         "required past a few hundred nodes")
    # observability flags (repro.obs; grid + breakdown modes)
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="compile screening forensics into the grid (bit-inert) "
                         "and write DIR/events.jsonl + DIR/obs_summary.json "
                         "(render with `python -m repro.obs.report DIR`)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the grid run into DIR")
    ap.add_argument("--metrics", default=None, metavar="DIR",
                    help="compile the live metric ring into every cell (grid "
                         "mode, bit-inert) and stream per-tick rows tagged by "
                         "cell to DIR/metrics.jsonl; watch with "
                         "`python -m repro.obs.monitor DIR`")
    ap.add_argument("--metrics-capacity", type=int, default=64,
                    help="on-device metric ring slots per cell; grids stream "
                         "the last `capacity` ticks of each chunk")
    # trust flags (repro.trust; grid + breakdown modes)
    ap.add_argument("--trust", action="store_true",
                    help="compile reputation-weighted screening + eviction "
                         "into every cell (repro.trust) — pair with rep_* "
                         "rules for soft down-weighting")
    ap.add_argument("--trust-evict", type=float, default=0.5,
                    help="suspicion threshold that latches an edge out")
    ap.add_argument("--trust-warmup", type=int, default=8,
                    help="ticks before evictions can latch")
    args = ap.parse_args(argv)
    from repro.launch.cache import use_compilation_cache

    use_compilation_cache()
    if args.out is None:
        args.out = {"net": "experiments/net", "grid": "experiments/grid",
                    "breakdown": "experiments/breakdown"}.get(
            args.mode, "experiments/dryrun")
    os.makedirs(args.out, exist_ok=True)
    if args.mode == "breakdown":
        run_breakdown_mode(args)
        return
    if args.mode == "grid":
        if args.scenarios is None:
            args.scenarios = "sync"  # default grid mode is the broadcast path
        if args.attacks is None:
            # selective_victim needs the net runtime; default per path
            sync = args.scenarios in ("sync", "none", "")
            args.attacks = "random,alie" if sync else "random,alie,selective_victim"
        run_grid_mode(args)
        return
    if args.scenarios is None:
        args.scenarios = ",".join(NET_SCENARIOS)
    if args.attacks is None:
        args.attacks = "random,alie,selective_victim"
    workers = _fanout_workers(args.jobs)
    if args.mode == "net":
        jobs = [(r, a, s)
                for r in args.rules.split(",")
                for a in args.attacks.split(",")
                for s in args.scenarios.split(",")]
        print(f"{len(jobs)} net-scenario jobs ({workers} at a time) -> {args.out}")
        with ThreadPoolExecutor(max_workers=workers) as ex:
            futs = [ex.submit(run_net_job, r, a, s, args.out, args.timeout,
                              args.net_arch, args.net_steps) for r, a, s in jobs]
            for fut in futs:
                tag, status = fut.result()
                print(f"  {tag:60s} {status}", flush=True)
        return
    archs = args.archs.split(",") if args.archs else ARCHS
    shapes = args.shapes.split(",") if args.shapes else SHAPES
    jobs = []
    for arch in archs:
        for shape in shapes:
            jobs.append((arch, shape, False))
            if not args.single_pod_only:
                jobs.append((arch, shape, True))
    print(f"{len(jobs)} jobs ({workers} at a time) -> {args.out}")
    with ThreadPoolExecutor(max_workers=workers) as ex:
        futs = {ex.submit(run_job, a, s, mp, args.out, args.timeout): (a, s, mp)
                for a, s, mp in jobs}
        for fut in __import__("concurrent.futures", fromlist=["as_completed"]).as_completed(futs):
            tag, status = fut.result()
            print(f"  {tag:60s} {status}", flush=True)


if __name__ == "__main__":
    main()

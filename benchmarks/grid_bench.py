"""Grid-engine throughput benchmark: one-compile vmapped sweep vs the
subprocess sweep baseline.

Runs a rule x attack x seed grid on the paper's MNIST-like linear task three
ways:

* **grid** — every cell inside one jitted program (`repro.sim.GridEngine`);
  wall time INCLUDES the single compilation.
* **subprocess baseline** — real ``python -m repro.launch.sweep --mode grid``
  single-cell invocations (fresh interpreter + jax import + data + trace +
  compile per cell — exactly what the subprocess fan-out pays), measured on
  ``baseline_cells`` cells and extrapolated.
* **sequential in-process baseline** — a fresh `BridgeTrainer` per cell in
  this process (no interpreter/import cost): the lower bound any
  per-cell-process design could hope for.

Emits ``BENCH_grid.json`` (cells/sec each way, speedup, trace count) for the
CI artifact + regression gate, and CSV rows for `benchmarks.run`.  The grid
run also cross-checks a sample cell against its in-process sequential twin
(recording the max deviation — the protocol pipeline is bit-identical by
construction, the model's multithreaded CPU GEMMs may drift at ULP level),
so the speedup number can't silently come from computing something
different.

    PYTHONPATH=src python -m benchmarks.grid_bench [--smoke] [--chunk N]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import eval_accuracy, get_data, make_grad_fn
from repro.core import BridgeConfig, BridgeTrainer, replicate
from repro.data import partition_iid
from repro.data.partition import stack_node_batches
from repro.models import small
from repro.sim import ExperimentGrid, GridEngine
from repro.sim.engine import stack_batches

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(_ROOT, "BENCH_grid.json")


def _subprocess_cell_seconds(cells, num_nodes, ticks) -> float:
    """Mean wall time of a real one-cell subprocess sweep (the per-cell cost
    of the subprocess fan-out this engine replaces)."""
    walls = []
    for c in cells:
        out = tempfile.mkdtemp(prefix="grid_base_")
        cmd = [
            sys.executable, "-m", "repro.launch.sweep", "--mode", "grid",
            "--rules", c.rule, "--attacks", c.attack, "--byz", str(c.b),
            "--seeds", str(c.seed), "--grid-nodes", str(num_nodes),
            "--grid-ticks", str(ticks), "--out", out,
        ]
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(_ROOT, "src") + os.pathsep + env.get("PYTHONPATH", "")
        t0 = time.perf_counter()
        proc = subprocess.run(cmd, capture_output=True, text=True, cwd=_ROOT, env=env)
        walls.append(time.perf_counter() - t0)
        shutil.rmtree(out, ignore_errors=True)
        if proc.returncode != 0:
            raise RuntimeError(f"baseline subprocess failed: {proc.stderr[-2000:]}")
    return float(np.mean(walls))


def grid_throughput(
    num_nodes: int = 12,
    ticks: int = 30,
    *,
    rules=("trimmed_mean", "median"),
    attacks=("random", "alie", "sign_flip"),
    num_byzantine: int = 2,
    seeds=tuple(range(8)),
    chunk: int | None = None,
    baseline_cells: int = 2,
    subprocess_baseline: bool = True,
    seed: int = 0,
):
    """Returns CSV rows and writes BENCH_grid.json."""
    from repro.sim.grid import default_topology

    topo = default_topology(num_nodes, rules, (num_byzantine,), seed=seed)
    grid = ExperimentGrid(topo, rules, attacks, (num_byzantine,), seeds, lam=1.0, t0=30.0)
    n_base = min(baseline_cells, grid.num_cells)
    # subprocess baseline first, while this process has not yet touched a
    # JAX backend: a chip belongs to one process, so children started after
    # the parent holds it would fail or hang
    if subprocess_baseline:
        sub_s = _subprocess_cell_seconds(grid.cells()[:n_base], num_nodes, ticks)
        sub_cps = 1.0 / sub_s
    else:
        sub_s = sub_cps = None

    x, y, xt, yt = get_data()
    shards = partition_iid(x, y, num_nodes, seed=seed)
    # stack_node_batches closures are stateful (the rng advances per call):
    # every consumer gets a FRESH closure so all paths see the same draws
    fresh_batch_fn = lambda: stack_node_batches(shards, 32, seed=seed)
    grad_fn = make_grad_fn("linear")
    bf = fresh_batch_fn()
    batches = stack_batches(
        lambda i: jax.tree_util.tree_map(jnp.asarray, bf(i)), ticks)

    def init_fn(s):
        key = jax.random.PRNGKey(s)
        return replicate(small.init_linear(key), num_nodes, perturb=0.01, key=key)

    engine = GridEngine(grid, grad_fn)
    e = engine.num_cells

    t0 = time.perf_counter()
    state0 = engine.init(init_fn)
    state, metrics = engine.run(state0, batches, chunk=chunk)
    jax.block_until_ready(state.params)
    wall_grid = time.perf_counter() - t0
    grid_cps = e / wall_grid
    # the sweep's one compile is part of the amortized story (wall_s keeps
    # it), but re-running the now-cached program splits it out so the gate
    # can track scan cost and compile cost separately
    t0 = time.perf_counter()
    jax.block_until_ready(engine.run(state0, batches, chunk=chunk)[0].params)
    wall_steady = time.perf_counter() - t0
    compile_s = max(wall_grid - wall_steady, 0.0)

    # in-process sequential baseline: fresh trainer (trace + compile) per cell
    t0 = time.perf_counter()
    base_final = {}
    for c in engine.cells[:n_base]:
        cfg = BridgeConfig(topology=topo, rule=c.rule, num_byzantine=c.b,
                           attack=c.attack, lam=1.0, t0=30.0)
        tr = BridgeTrainer(cfg, make_grad_fn("linear"))
        st = tr.init(init_fn(c.seed), seed=c.seed)
        bf = fresh_batch_fn()  # same draw sequence the grid scanned over
        for i in range(ticks):
            bx, by = bf(i)
            st, _ = tr.step(st, (jnp.asarray(bx), jnp.asarray(by)))
        jax.block_until_ready(st.params)
        base_final[c.tag] = st.params
    wall_seq = time.perf_counter() - t0
    seq_cps = n_base / wall_seq
    if sub_cps is None:  # smoke-speed escape hatch
        sub_cps = seq_cps

    # correctness anchor: the measured speedup compares identical experiments.
    # The protocol pipeline (attack/screen/update) is bit-identical by
    # construction (property-tested in tests/test_grid.py); the model's GEMM
    # reductions may drift at ULP level under multithreaded CPU batching, so
    # the bench records the observed max deviation and gates on allclose.
    sample = engine.cells[0]
    diffs = [
        float(np.max(np.abs(np.asarray(leaf_g[0], np.float64) - np.asarray(leaf_s, np.float64))))
        for leaf_g, leaf_s in zip(
            jax.tree_util.tree_leaves(state.params),
            jax.tree_util.tree_leaves(base_final[sample.tag]),
            strict=True,
        )
    ]
    max_diff = max(diffs)
    same = max_diff <= 1e-5
    speedup = grid_cps / sub_cps
    acc = eval_accuracy(
        "linear",
        jax.tree_util.tree_map(lambda leaf: leaf[0], state.params),
        ~engine.byz_masks[0], jnp.asarray(xt), jnp.asarray(yt),
    )
    record = {
        "grid": {
            "cells": e, "ticks": ticks, "num_nodes": num_nodes,
            "chunk": chunk, "wall_s": wall_grid, "cells_per_sec": grid_cps,
            "compile_s": compile_s, "steady_state_s": wall_steady,
            "trace_count": engine.trace_count,
            "rules": list(rules), "attacks": list(attacks), "seeds": list(seeds),
        },
        "subprocess_baseline": {
            "cells_measured": n_base, "seconds_per_cell": sub_s,
            "cells_per_sec": sub_cps,
            "extrapolated_wall_s_all_cells": e / sub_cps,
        },
        "sequential_inprocess_baseline": {
            "cells_measured": n_base, "wall_s": wall_seq, "cells_per_sec": seq_cps,
        },
        "speedup_vs_subprocess": speedup,
        "speedup_vs_sequential_inprocess": grid_cps / seq_cps,
        "sample_cell_allclose": bool(same),
        "sample_cell_max_abs_diff": max_diff,
        "sample_cell_accuracy": float(acc),
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    if not same:
        raise RuntimeError(
            f"grid/sequential divergence: sample cell {sample.tag} differs by "
            f"{max_diff:.3g} (> 1e-5) — the speedup would compare different "
            f"computations; see BENCH_grid.json"
        )
    rows = [
        ("grid/engine", wall_grid / e * 1e6,
         f"cells={e};cells_per_sec={grid_cps:.3f};trace_count={engine.trace_count}"),
        ("grid/subprocess_baseline", 0.0 if sub_s is None else sub_s * 1e6,
         f"cells={n_base};cells_per_sec={sub_cps:.3f}"),
        ("grid/sequential_baseline", wall_seq / n_base * 1e6,
         f"cells={n_base};cells_per_sec={seq_cps:.3f}"),
        ("grid/speedup", 0.0,
         f"x{speedup:.1f}_vs_subprocess;sample_allclose={same};acc={acc:.4f}"),
    ]
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grid for quick local runs (fewer seeds, "
                         "no subprocess baseline)")
    ap.add_argument("--nodes", type=int, default=12)
    ap.add_argument("--ticks", type=int, default=30)
    ap.add_argument("--chunk", type=int, default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        kw = dict(seeds=(0, 1), ticks=20, baseline_cells=1, subprocess_baseline=False)
    else:
        kw = dict(ticks=args.ticks)
    print("name,us_per_call,derived")
    for name, us, derived in grid_throughput(args.nodes, chunk=args.chunk, **kw):
        print(f"{name},{us:.1f},{derived}", flush=True)


if __name__ == "__main__":
    main()

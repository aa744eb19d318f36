#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... --control-seeds 1,2,3

Host memory grows from seed to seed in one process: at the stream cells'
size, run one seed a process (a shell loop over ``--seeds <s>``).

Not part of a benchmark run.  For each seed it drives the cell's program
through the ticks the reference follows (the set-up of a run, no window)
and compares it with the float32 reference, as a run does: the lower
reading of each number is the largest over these sound runs.  For each
control seed it also reads:

* the control: the configuration's precision lowered one step (float32 ->
  bfloat16), through the program's own path where it has one (a model
  module with ``program_config(cfg, dtype)``), else the reference in
  bfloat16 in the program's place;
* the faults, planted in the reference put in the program's place: half of
  each node's batch left out (the mean taken over the rest; half of the
  sequence where a node's batch is one row), and, on a path
  whose nodes exchange between chips, the exchange left out.  A state left
  unchanged reads ``change_gap`` = 1 by construction and needs no run.

Prints one JSON line per reading and a summary line last.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")]


def half_rows(a, axis):
    """``a`` cut to the first half of its rows along ``axis``, the batch
    axis, or, where the batch is one row, to the first half of that row's
    sequence (the next axis: a row of S + 1 tokens keeps S / 2 + 1, so the
    loss is the mean over the first S / 2 positions)."""
    if a.shape[axis] == 1:
        return a[(slice(None),) * (axis + 1) + (slice(a.shape[axis + 1] // 2 + 1),)]
    return a[(slice(None),) * axis + (slice(a.shape[axis] // 2),)]


def half_batches(batches):
    """Each node's batch halved by `half_rows` (axis 1, after the node axis)."""
    import jax

    return [jax.tree_util.tree_map(lambda a: half_rows(a, 1), b) for b in batches]


def calibrate(work: dict, cfg: dict, traffic: dict, seeds, control_seeds, cell_cls=None,
              out=sys.stdout) -> dict:
    """Readings of the program, the control and the faults; returns the
    summary: each number's largest program reading and smallest control and
    fault readings."""
    import importlib

    import jax

    from bench import check, models, run
    from bench.reference import bridge as ref_bridge

    Cell = cell_cls or importlib.import_module(f"bench.paths.{traffic['path']}").Cell
    readings = {"program": [], "control": [], "half_batch": [], "no_exchange": []}

    def emit(kind, seed, got, t):
        row = {"kind": kind, "seed": seed, "seconds": time.perf_counter() - t, **got}
        readings[kind].append(row)
        print(json.dumps(row), file=out, flush=True)

    for seed in seeds:
        t = time.perf_counter()
        cell = Cell(cfg, traffic, seed)
        got, ref, spec = run.judge(cell, *run.first_ticks(cell))
        del cell
        emit("program", seed, got, t)
        if seed not in control_seeds:
            continue
        params0 = spec["params0"]
        t = time.perf_counter()
        if not hasattr(models.of(cfg), "program_config"):  # no lower-precision path
            losses, change, _ = check.reference_readings(spec, params0(), dtype="bfloat16")
        else:
            cell = Cell(cfg, traffic, seed, dtype="bfloat16")
            losses, first_params = run.first_ticks(cell)
            cell.release()
            start = jax.tree_util.tree_map(lambda a: a.astype("bfloat16"), params0())
            change = ref_bridge.change_norms(first_params, start, spec["honest"])
            del cell, first_params, start
        emit("control", seed, check.compare(losses, change, *ref), t)
        t = time.perf_counter()
        losses, change, _ = check.reference_readings(spec, params0(),
                                                     batches=half_batches(spec["batches"]))
        emit("half_batch", seed, check.compare(losses, change, *ref), t)
        if work["chips"] > 1:
            t = time.perf_counter()
            losses, change, _ = check.reference_readings(spec, params0(), exchange=False)
            emit("no_exchange", seed, check.compare(losses, change, *ref), t)
    summary = {}
    for kind, rows in readings.items():
        if rows:
            pick = max if kind == "program" else min
            summary[kind] = {n: pick(r[n] for r in rows) for n in ("loss_gap", "change_gap")}
    return summary


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)
    import jax

    from bench import common

    work, cfg, traffic, _ = common.cell_files(args.workload)
    common.require_accelerator(work["chips"])
    from repro.launch.cache import use_compilation_cache

    use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    summary = calibrate(work, cfg, traffic, [int(s) for s in args.seeds.split(",")],
                        {int(s) for s in args.control_seeds.split(",")})
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

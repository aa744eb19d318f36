#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs`` -> its file under ``bench/configs/``) under a traffic mix
(``bench/traffic/<traffic>.json``, whose ``path`` names the driver in
``bench/paths/``).  Its limits are ``bench/limits/<cell>.json``; each metric
is read by ``bench/metrics/<metric>.py``.  All are found by name.

A run:

1. set-up: makes the batches and the weights from ``--seed`` (the graph,
   the Byzantine set and every shape are the traffic file's), builds the
   trainer, and drives the window's own call through the ticks the
   reference will follow, which compiles (or loads from the compile cache)
   every program the window runs;
2. window: calls the cell's entry until ``--seconds`` have passed (two
   calls in flight, so the device is never waiting on the host's turn),
   then blocks on the state; nothing may compile inside it.  With
   ``--trace 1`` it instead calls the entry ``trace_calls`` times under the
   profiler, and the trace is reduced in ``bench/trace.py``;
3. reads the peak device memory, frees the program's state, and runs the
   plain float32 reference (``bench/reference/``) over the same first
   ticks to decide ``correct`` (``bench/check.py``).

The last line of standard output is one JSON object; the numbers compared
are also the last lines of standard error.  The run fails, and prints no
result, where JAX finds no accelerator, fewer chips than the cell asks
for, a device kind not in ``bench/peaks.json``, or no program checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _loss_values(handles) -> list:
    import numpy as np

    return [float(v) for h in handles for v in np.ravel(np.asarray(h, np.float64))]


def peak_bytes(devices) -> int:
    """Peak device memory of the fullest device: the allocator's
    ``peak_bytes_in_use`` (arrays: arguments, outputs, everything held
    between calls) plus ``peak_bytes_reserved``, the region a program
    reserves for its temporaries, which ``peak_bytes_in_use`` leaves out."""
    def one(d):
        st = d.memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0)) + int(st.get("peak_bytes_reserved", 0))
    return max(one(d) for d in devices)


def first_ticks(cell):
    """Set the cell up and drive the window's own call through the ticks
    the reference follows: (their losses, the parameters after them, on
    the host)."""
    cell.setup()
    first = [cell.call(k) for k in range(cell.check_calls)]
    cell.sync()
    return _loss_values(first), cell.snapshot()


def judge(cell, first_losses, first_params):
    """Free the cell's state, run the reference over its first ticks, and
    compare: (`bench.check.compare`'s readings, the reference's readings,
    the spec), the last two for `bench/calibrate.py` to reuse."""
    import jax

    from bench import check
    from bench.reference import bridge as ref_bridge

    spec = cell.reference_spec()
    prog_dtype = cell.dtype
    cell.release()
    gc.collect()
    in_use = max(int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in jax.devices())
    print(f"bench: {in_use} bytes in use on the fullest device after the program's state "
          f"was freed", file=sys.stderr)
    params0 = jax.tree_util.tree_map(lambda a: a.astype(prog_dtype), spec["params0"]())
    prog_change = ref_bridge.change_norms(first_params, params0, spec["honest"])
    del params0
    ref = check.reference_readings(spec, spec["params0"]())
    return check.compare(first_losses, prog_change, *ref), ref, spec


def run_cell(work: dict, cfg: dict, traffic: dict, limits: dict, *, seed: int,
             seconds: float, trace: bool, devices, peak: dict, t_start: float,
             metric_entries: list, cell=None, keep_trace: str | None = None) -> dict:
    """One run of one cell; returns the result object.  ``metric_entries``
    are the ``BENCHMARK.json`` metrics to report; ``cell`` replaces the
    path's driver (the tests break the timed path through it);
    ``keep_trace`` is a directory to keep a traced run's reduced events and
    HLO texts in (the inputs of `bench.trace.summarize`)."""
    import jax

    from bench import common, work as work_counts
    from bench import trace as trace_lib

    if cell is None:
        cell = importlib.import_module(f"bench.paths.{traffic['path']}").Cell(cfg, traffic, seed)
    devices = devices[:work["chips"]]

    # 1. set-up, ending with the ticks the reference follows
    with common.CompileClock() as setup_clock:
        first_losses, first_params = first_ticks(cell)
    setup_s = time.perf_counter() - t_start

    # 2. the window
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    ann = jax.profiler.TraceAnnotation
    with common.CompileClock() as window_clock:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        gc.disable()  # no collector pauses inside the window
        with ann("bench.window"):
            t0 = time.perf_counter()
            k, pending, handles = cell.check_calls, collections.deque(), []
            while True:
                with ann("bench.call"):
                    h = cell.call(k)
                k += 1
                pending.append(h)
                handles.append(h)
                if len(pending) > 1:
                    with ann("bench.wait"):
                        jax.block_until_ready(pending.popleft())
                done = k - cell.check_calls
                if (done >= traffic["trace_calls"]) if trace else (time.perf_counter() - t0 >= seconds):
                    break
            with ann("bench.block"):
                cell.sync()
            elapsed = time.perf_counter() - t0
        gc.enable()
        if trace:
            jax.profiler.stop_trace()
    if window_clock.lowerings or window_clock.compiles:
        raise RuntimeError(f"{window_clock.lowerings} lowering(s) and {window_clock.compiles} "
                           f"compile(s) inside the window")
    ticks = (k - cell.check_calls) * cell.ticks_per_call
    window_losses = _loss_values(handles)
    failed = sum(not math.isfinite(v) for v in window_losses)
    memory_peak = peak_bytes(devices)

    summary = None
    if trace:
        texts = cell.compiled_window()
        for ma in texts[1]:
            print(f"bench: window program on the chip: {ma}", file=sys.stderr)
        texts = texts[0]
        events = trace_lib.extract(trace_lib.find_xplane(log_dir))
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            with open(os.path.join(keep_trace, "events.json"), "w") as f:
                json.dump(events, f)
            for i, text in enumerate(texts):
                with open(os.path.join(keep_trace, f"hlo_{i}.txt"), "w") as f:
                    f.write(text)
        summary = trace_lib.summarize(events, texts)
        shutil.rmtree(log_dir, ignore_errors=True)

    # 3. the reference, with the program's state freed
    del handles, pending, h
    got = judge(cell, first_losses, first_params)[0]
    del cell, first_params
    compared = {n: {"value": got[n], "limit": limits[n]} for n in ("loss_gap", "change_gap")}
    correct = failed == 0 and all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                                  for c in compared.values())

    counts = work_counts.tick_counts(cfg, traffic)
    ctx = {"setup_s": setup_s, "compile_s": setup_clock.seconds, "window_s": elapsed,
           "ticks": ticks, "tokens": ticks * counts["tokens"], "peak_bytes": memory_peak,
           "trace": summary, "work": counts, "peak": peak, "chips": work["chips"],
           "config": cfg, "traffic": traffic}
    metrics = {}
    for m in metric_entries:
        value = common.read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": ticks, "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1, keep the trace's events and HLO texts in DIR")
    args = ap.parse_args(argv)
    from bench import common

    work, cfg, traffic, limits = common.cell_files(args.workload)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit("bench: no program (src/repro) in this checkout")
    import jax

    devices = common.require_accelerator(work["chips"])
    peak = common.peaks(devices[0].device_kind)
    from repro.launch.cache import use_compilation_cache

    use_compilation_cache()
    # cache every program, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = run_cell(work, cfg, traffic, limits, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices, peak=peak, t_start=T_START,
                   metric_entries=common.cell_metrics(args.workload, bool(args.trace)),
                   keep_trace=args.keep_trace)
    for name, c in out["compared"].items():
        print(f"{name} {c['value']:.6g} limit {c['limit']:.6g}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

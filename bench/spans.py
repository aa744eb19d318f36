#!/usr/bin/env python3
"""The program's own host spans, on the device trace's clock.

`BridgeTrainer.run_chunks` (``repro.core.bridge``) writes ``jax.profiler``
spans named ``bridge.*``, which carry their counters as arguments:
``bridge.run_chunks`` (``lo``, ``hi``) around a call and, inside it per
chunk, ``bridge.put`` (``bytes``, ``ticks``), ``bridge.stack`` (``ticks``),
``bridge.dispatch`` (``lo``, ``hi``, ``traced``) and ``bridge.flush``, then
``bridge.collect``.  They land on the same host plane of the same
``.xplane.pb`` as the benchmark's ``bench.*`` spans.

1. `extract` reads them, with their arguments, from the ``.xplane.pb``.
2. `reduce` puts them and the ``bench.*`` spans of `bench.trace.extract`'s
   events on the device timeline, shifted by the ``clock_shift_s`` that
   `bench.trace.summarize` returns, and gives per span name: the count, the
   self time (the spans less the part their child spans cover), the sum of
   each counter, and the device idle time (gaps of at least
   `bench.trace.MIN_GAP_NS` in the union of the device's ops, inside the
   traced window) that falls inside the innermost span at that moment.  It
   also gives the smallest time from a ``bridge.dispatch`` span's start to
   the start of the program it launched on the device, which is positive
   when the spans and the device share one clock.

A program without these spans (one that predates them) gives no program
span names, and the readers of ``bench/metrics/put_*.paper.py`` then
return None.

Run as a script, it makes one traced run of a cell, as ``bench/run.py
--trace 1`` does, and prints (and with ``--out`` writes to ``spans.json``)
the reduction and those readers' values:

    python3 bench/spans.py --workload paper-linear.m128k16 --seed <n> [--out DIR]

``bench/run.py`` does not yet put the reduction in its metrics' context, so
the ``put_*.paper`` metrics are read here only.
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import trace  # noqa: E402

PROGRAM = ("bridge.",)  # the name prefixes of the program's spans
NO_SPAN = "no host span"
PUT_METRICS = ("put_ms.paper", "put_mb.paper", "put_idle_ms.paper")


def extract(xplane_path: str) -> list:
    """[[name, start_ns, duration_ns, {argument: value}]] of the program's
    host spans in one ``.xplane.pb``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(xplane_path)
    return [[e.name, e.start_ns, e.duration_ns, dict(e.stats)]
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events if e.name.startswith(PROGRAM)]


def _innermost(spans) -> list:
    """Disjoint [(start, end, name)] segments of the spans' union, each
    named by the innermost span covering it (the latest started of those
    still open)."""
    points = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(spans, key=lambda x: (x[1], -x[2]))
    segs, stack, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(order) and order[i][1] <= a:
            stack.append(order[i])
            i += 1
        stack = [x for x in stack if x[2] > a]
        if stack:
            segs.append((a, b, stack[-1][0]))
    return segs


def _idle_gaps(ops, lo, hi) -> list:
    """[(start, end)] gaps of at least `trace.MIN_GAP_NS` in the union of
    ``ops`` ([(start, end)]) inside [lo, hi], as `trace.summarize` finds them."""
    gaps, cur = [], lo
    for s, e in trace._clip(trace._union(ops), lo, hi) + [(hi, hi)]:
        if s - cur >= trace.MIN_GAP_NS:
            gaps.append((cur, s))
        cur = max(cur, e)
    return gaps


def _overlap(segs, gaps) -> dict:
    """{name: ns of ``gaps`` inside the segments of that name}, ``NO_SPAN``
    for the part in none (both lists sorted and disjoint)."""
    out, j = {}, 0
    for g0, g1 in gaps:
        covered = 0
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            part = min(g1, segs[k][1]) - max(g0, segs[k][0])
            out[segs[k][2]] = out.get(segs[k][2], 0) + part
            covered += part
            k += 1
        if g1 - g0 > covered:
            out[NO_SPAN] = out.get(NO_SPAN, 0) + (g1 - g0 - covered)
    return out


def launch_margin_s(program, events, clock_shift_s):
    """The least time from the start of the k-th ``bridge.dispatch`` span to
    the start of the k-th run of its program (``jit_scan_chunk``) on each
    device (seconds; negative where a program appears to start before the
    call that launched it), or None where the counts differ."""
    starts = sorted(s for n, s, _, _ in program if n == "bridge.dispatch")
    best = None
    for dev in events["devices"].values():
        runs = sorted(s + clock_shift_s * 1e9 for n, s, _ in dev["modules"]
                      if n == "jit_scan_chunk")
        if not runs or len(runs) != len(starts):
            return None
        m = min(r - s for r, s in zip(runs, starts)) * 1e-9
        best = m if best is None else min(best, m)
    return best


def reduce(program, events, clock_shift_s, *, window: str = "bench.window") -> dict:
    """The program's spans (`extract`) with the device timeline of
    `bench.trace.extract`'s ``events``, shifted by ``clock_shift_s``.

    Returns ``window_s``, ``idle_s`` (the device idle time in gaps, averaged
    over the devices), ``spans``: {name: {"count", "self_s", "idle_s",
    "counters"}} for the program's spans and the ``bench.*`` spans around
    them (idle time in no span under ``NO_SPAN``), and
    ``dispatch_margin_s`` (`launch_margin_s`)."""
    wins = [h for h in events["host"] if h[0] == window]
    if len(wins) != 1:
        raise ValueError(f"expected one {window!r} host span, found {len(wins)}")
    lo, hi = wins[0][1], wins[0][1] + wins[0][2]
    shift = clock_shift_s * 1e9
    host = [(n, s, s + d) for n, s, d in events["host"]] + \
           [(n, s, s + d) for n, s, d, _ in program]
    segs = _innermost(host)
    out = {}

    def entry(name):
        return out.setdefault(name, {"count": 0, "self_s": 0.0, "idle_s": 0.0,
                                     "counters": {}})

    for name, s, d, stats in program:
        e = entry(name)
        e["count"] += 1
        for k, v in stats.items():
            if k not in ("lo", "hi") and isinstance(v, (int, float)):
                e["counters"][k] = e["counters"].get(k, 0) + v
    for name, *_ in events["host"]:
        entry(name)["count"] += 1
    for s, e, name in segs:
        entry(name)["self_s"] += (e - s) * 1e-9
    devs = events["devices"].values()
    idle = 0.0
    for dev in devs:
        gaps = _idle_gaps([(s + shift, s + d + shift) for _, s, d in dev["ops"]], lo, hi)
        idle += sum(e - s for s, e in gaps)
        for name, ns in _overlap(segs, gaps).items():
            entry(name)["idle_s"] += ns * 1e-9 / len(devs)
    return {"window_s": (hi - lo) * 1e-9, "idle_s": idle * 1e-9 / max(len(devs), 1),
            "spans": out, "dispatch_margin_s": launch_margin_s(program, events, clock_shift_s)}


def per_tick(ctx, span: str, key: str, scale: float):
    """``ctx["spans"]``' ``key`` of ``span`` (``self_s``, ``idle_s`` or a
    counter's name) per tick, times ``scale``; None where the program wrote
    no such span."""
    sp = (ctx.get("spans") or {}).get("spans", {}).get(span)
    if sp is None or ctx["ticks"] == 0:
        return None
    value = sp["counters"].get(key) if key not in sp else sp[key]
    return None if value is None else value / ctx["ticks"] * scale


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description="one traced run of a cell, with the program's spans")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", metavar="DIR",
                    help="write spans.json, and keep the trace's events and HLO texts, in DIR")
    args = ap.parse_args(argv)
    from bench import common, run

    work, cfg, traffic, limits = common.cell_files(args.workload)
    import jax

    devices = common.require_accelerator(work["chips"])
    from repro.launch.cache import use_compilation_cache

    use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # `run_cell` reduces the trace and deletes it: read the program's spans
    # from the same file on the way
    kept, read = {}, trace.extract

    def keep(path):
        kept["program"], kept["events"] = extract(path), read(path)
        return kept["events"]

    trace.extract = keep
    try:
        out = run.run_cell(work, cfg, traffic, limits, seed=args.seed, seconds=0.0,
                           trace=True, devices=devices,
                           peak=common.peaks(devices[0].device_kind), t_start=run.T_START,
                           metric_entries=common.cell_metrics(args.workload, True),
                           keep_trace=args.out)
    finally:
        trace.extract = read
    shift = trace.summarize(kept["events"])["clock_shift_s"]
    ctx = {"spans": reduce(kept["program"], kept["events"], shift), "ticks": out["attempted"]}
    got = {"workload": args.workload, "seed": args.seed, "result": out,
           "ticks": ctx["ticks"], "spans": ctx["spans"],
           "metrics": {m: common.read_metric(m, ctx) for m in PUT_METRICS}}
    if args.out:
        with open(os.path.join(args.out, "spans.json"), "w") as f:
            json.dump({**got, "program": kept["program"]}, f)
    print(json.dumps(got))
    return 0


if __name__ == "__main__":
    sys.exit(main())

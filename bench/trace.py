"""From a profiler trace to device times: the benchmark's own reduction.

Two steps, so that the second can be tested on a small recorded trace:

1. `extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
   what the reduction needs, as plain lists: per TPU device, the ``XLA Ops``
   events (HLO instruction name, start, duration, in ns), the ``XLA
   Modules`` events (which program ran when), the ``Async XLA Ops`` spans
   (start of an async op to its done), and the host spans the benchmark
   writes itself (``bench.*`` TraceAnnotations).
2. `summarize` turns that, with the HLO text of the programs the window ran
   (which maps each instruction to its ``op_name``, the ``jax.named_scope``
   path), into: the traced window's length, the device's busy time (the
   union of op intervals), self time per named scope, collective time and
   the part of it no compute hides, the top device ops, and the longest idle
   gaps named by the host span they fall in.

Collectives are told from the compiled HLO, not from op names alone.  The
v5e compiler turns most of a step's ``all-gather``s into an
``async-collective-start`` fusion, a few compute fusions that carry the
transfer alongside their own work (a ``convolution`` and an ``all-gather``
in one called computation), and an ``async-collective-done`` fusion; only
some stay plain ``all-gather`` ops.  An op whose instruction (or called
computation) holds a collective and no matmul is collective time; one that
holds both is compute that hides a collective, counted in the collective
time and not in its exposed part.

The ``XLA Ops`` line nests: a ``while`` event spans the ops of its body.  An
op's *self* time is its duration less that of the events nested in it, so
that time is counted once.

The device's timestamps run up to a few milliseconds apart from the host's
(on a v5e the device clock read about 1.5 ms behind: a program's ops
appeared before the host call that launched it).  The trace is started
after set-up has synchronised and stopped after the window's final block,
so every device op in it belongs to the window; the device timeline is
shifted so that its last op ends where the host's final block returns (to
within the completion's notice, well under a millisecond).  An idle gap is
then named by the host span that overlaps it most.
"""
from __future__ import annotations

import bisect
import glob
import re

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
                  "all-to-all", "collective-broadcast", "ragged-all-to-all",
                  "async-collective")  # the last: the v5e's async wrappers, by name
COMPUTE_OPS = ("convolution", "dot")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_INSTR_LINE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_CALLS = re.compile(r"calls=%([^\s,]+)")
_INSTR = re.compile(r"^%?([^\s=]+)\s*=")
_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*?metadata=\{[^}]*op_name=\"([^\"]*)\"")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_SUFFIX = re.compile(r"[.]\d+$")
MIN_GAP_NS = 1000  # shorter idle gaps are the seams between back-to-back ops


def instr_name(event_name: str) -> str:
    """``'%fusion.28 = f32[..] fusion(..)'`` -> ``'fusion.28'``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def module_name(event_name: str) -> str:
    """``'jit_scan_chunk(1638..)'`` -> ``'jit_scan_chunk'``."""
    return event_name.split("(", 1)[0]


def extract(xplane_path: str) -> dict:
    """The events `summarize` reads, from one ``.xplane.pb``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(xplane_path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = {"ops": [], "modules": [], "async": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules",
                       "Async XLA Ops": "async"}.get(line.name)
                if key is None:
                    continue
                name = module_name if key == "modules" else instr_name
                dev[key] = [[name(e.name), e.start_ns, e.duration_ns] for e in line.events]
            out["devices"][m.group(1)] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events if e.name.startswith("bench.")]
    return out


def find_xplane(log_dir: str) -> str:
    found = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def op_names(hlo_text: str) -> tuple[str, dict]:
    """(module name, {instruction: op_name}) from a compiled HLO text."""
    mod = _MODULE.search(hlo_text)
    names = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            names[m.group(1)] = m.group(2)
    return (mod.group(1) if mod else ""), names


def collective_kinds(hlo_text: str) -> dict:
    """{instruction: "collective" | "overlap" | ""} from a compiled HLO text.

    "collective": a collective opcode, or a fusion whose called computations
    hold one and no matmul (an async start or done).  "overlap": a fusion
    whose called computations hold both, compute that carries a transfer.
    "": no collective."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            cur = m.group(1) if m else None
            if cur is not None:
                comps[cur] = []
            continue
        m = _INSTR_LINE.match(line)
        if m and cur is not None:
            op = _OPCODE.search(m.group(2))
            comps[cur].append((m.group(1), op.group(1) if op else "", _CALLS.findall(m.group(2))))
    held = {}

    def holds(ops, calls):
        coll = any(op.startswith(COLLECTIVE_OPS) for op in ops)
        comp = any(op in COMPUTE_OPS for op in ops)
        for c in calls:
            if c not in held:
                held[c] = (False, False)  # a called computation never calls back
                held[c] = holds([op for _, op, _ in comps.get(c, ())],
                                [x for _, _, cs in comps.get(c, ()) for x in cs])
            coll, comp = coll or held[c][0], comp or held[c][1]
        return coll, comp

    kinds = {}
    for instrs in comps.values():
        for name, op, calls in instrs:
            coll, comp = holds([op], calls)
            kinds[name] = ("overlap" if comp else "collective") if coll else ""
    return kinds


def _union(intervals):
    """Merged, sorted [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of union ``a`` not covered by union ``b`` (both merged)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _self_times(ops):
    """[(name, start, end, self_ns, is_leaf)] for ops that may nest (a while
    event spans the events of its body)."""
    ops = sorted(((n, s, s + d) for n, s, d in ops), key=lambda o: (o[1], -o[2]))
    res, stack = [], []  # stack of [index in res, end]
    for name, s, e in ops:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            res[stack[-1][0]][3] -= min(e, stack[-1][1]) - s
            res[stack[-1][0]][4] = False
        res.append([name, s, e, e - s, True])
        stack.append([len(res) - 1, e])
    return res


def _kind(kinds: dict, module: str, instr: str):
    """An executed op's collective kind (`collective_kinds`), or by its name
    where its program's HLO does not hold it."""
    if instr in kinds.get(module, ()):
        return kinds[module][instr] or None
    return "collective" if instr.startswith(COLLECTIVE_OPS) else None


def _module_at(modules, t):
    """The module running at time ``t`` (modules sorted, not overlapping)."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    return modules[i][2] if i >= 0 and modules[i][1] > t else ""


def summarize(events: dict, hlo_texts=(), *, window: str = "bench.window",
              top: int = 10) -> dict:
    """Reduce `extract`'s events over the host span ``window``.

    Times are seconds.  ``busy_s`` and ``scope_s`` are averaged over the
    devices; ``collective_s`` and ``collective_exposed_s`` are of the device
    with the most collective time."""
    maps = dict(op_names(t) for t in hlo_texts)
    kinds = {op_names(t)[0]: collective_kinds(t) for t in hlo_texts}
    spans = [h for h in events["host"] if h[0] == window]
    if len(spans) != 1:
        raise ValueError(f"expected one {window!r} host span, found {len(spans)}")
    lo, hi = spans[0][1], spans[0][1] + spans[0][2]
    host = [(n, s, s + d) for n, s, d in events["host"] if n != window]
    devs = events["devices"]
    if not devs:
        raise ValueError("the trace holds no TPU device")
    ends = [s + d for dev in devs.values() for _, s, d in dev["ops"]]
    shift = hi - max(ends) if ends else 0.0
    busy, scope, coll, exposed, op_time, gaps = [], {}, [], [], {}, []
    for dev in devs.values():
        modules = sorted((s + shift, s + d + shift, n) for n, s, d in dev["modules"])
        ops = [(n, s + shift, d) for n, s, d in dev["ops"]]
        union = _clip(_union([(s, s + d) for _, s, d in ops]), lo, hi)
        busy.append(_length(union))
        compute, coll_iv = [], []
        for name, s, e, self_ns, leaf in _self_times(ops):
            mod = _module_at(modules, s)
            label = maps.get(mod, {}).get(name) or f"{mod or 'unknown'}:{_SUFFIX.sub('', name)}"
            for part in set(label.split("/")):
                scope[part] = scope.get(part, 0.0) + self_ns
            op_time[label] = op_time.get(label, 0.0) + self_ns
            kind = _kind(kinds, mod, name)
            if kind:
                coll_iv.append((s, e))
            if leaf and kind != "collective":
                compute.append((s, e))
        coll_iv += [(s + shift, s + d + shift) for n, s, d in dev["async"]
                    if _kind(kinds, _module_at(modules, s + shift), n)]
        c_union, busy_compute = _union(coll_iv), _union(compute)
        coll.append(_length(c_union))
        exposed.append(_length(_subtract(c_union, busy_compute)))
        # idle gaps of this device, named by the host span overlapping most
        cur = lo
        for s, e in union + [(hi, hi)]:
            if s - cur >= MIN_GAP_NS:
                best = max(host, key=lambda h: min(h[2], s) - max(h[1], cur), default=None)
                over = best is not None and min(best[2], s) - max(best[1], cur) > 0
                gaps.append([best[0] if over else "no host span", (s - cur) * 1e-9])
            cur = max(cur, e)
    n = len(devs)
    worst = max(range(n), key=lambda i: coll[i])
    return {
        "window_s": (hi - lo) * 1e-9,
        "clock_shift_s": shift * 1e-9,
        "devices": n,
        "busy_s": sum(busy) / n * 1e-9,
        "scope_s": {k: v / n * 1e-9 for k, v in scope.items()},
        "collective_s": coll[worst] * 1e-9,
        "collective_exposed_s": exposed[worst] * 1e-9,
        "device_ops": [[k, v / n * 1e-9] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top],
    }

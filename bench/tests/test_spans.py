"""The program-span reduction (`bench.spans`) on hand-made events, and the
recorded traces' summaries (`bench.trace.summarize`) kept as they were."""
import gzip
import json
import os

import pytest

from bench import common, spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1000  # the hand-made events count in microseconds (the trace is in ns)

# one call of two chunks: put, stack, dispatch; put, dispatch; collect
PROGRAM = [
    ["bridge.run_chunks", 5 * US, 50 * US, {"lo": 0, "hi": 10}],
    ["bridge.put", 10 * US, 20 * US, {"bytes": 100, "ticks": 5}],
    ["bridge.stack", 30 * US, 5 * US, {"ticks": 5}],
    ["bridge.dispatch", 35 * US, 5 * US, {"lo": 0, "hi": 5, "traced": 1}],
    ["bridge.put", 40 * US, 5 * US, {"bytes": 50, "ticks": 5}],
    ["bridge.dispatch", 45 * US, 5 * US, {"lo": 5, "hi": 10, "traced": 0}],
    ["bridge.collect", 50 * US, 2 * US, {}],
]
# the device is idle over [12, 38] (inside the first put, stack and
# dispatch) and over [70, 72] (after the call, inside no program span)
EVENTS = {
    "devices": {"0": {
        "modules": [["jit_stack", 0, 12 * US], ["jit_scan_chunk", 38 * US, 32 * US],
                    ["jit_scan_chunk", 72 * US, 28 * US]],
        "ops": [["fusion.1", 0, 12 * US], ["while", 38 * US, 32 * US],
                ["while", 72 * US, 28 * US]],
        "async": [],
    }},
    "host": [["bench.window", 0, 100 * US], ["bench.call", 0, 60 * US]],
}


def reduced(events=EVENTS, shift=0.0):
    return spans.reduce(PROGRAM, events, shift)


def test_idle_is_charged_to_the_innermost_span():
    s = reduced()["spans"]
    # the gap [12, 38]: 18 us in the put, 5 in the stack, 3 in the dispatch,
    # none in the run_chunks span around them
    assert s["bridge.put"]["idle_s"] == pytest.approx(18e-6)
    assert s["bridge.stack"]["idle_s"] == pytest.approx(5e-6)
    assert s["bridge.dispatch"]["idle_s"] == pytest.approx(3e-6)
    assert s["bridge.run_chunks"]["idle_s"] == 0.0


def test_idle_outside_every_program_span_is_charged_to_none():
    r = reduced()
    program = sum(v["idle_s"] for k, v in r["spans"].items() if k.startswith("bridge."))
    assert r["idle_s"] == pytest.approx(28e-6)
    assert program == pytest.approx(26e-6)
    # the gap [70, 72] is the benchmark's own: in its window, outside its call
    assert r["spans"]["bench.window"]["idle_s"] == pytest.approx(2e-6)
    assert r["spans"]["bench.call"]["idle_s"] == 0.0


def test_self_time_subtracts_the_children():
    s = reduced()["spans"]
    # 50 us less put 20 + 5, stack 5, dispatch 5 + 5, collect 2
    assert s["bridge.run_chunks"]["self_s"] == pytest.approx(8e-6)
    assert s["bridge.put"]["self_s"] == pytest.approx(25e-6)
    assert s["bench.call"]["self_s"] == pytest.approx(10e-6)  # 60 less the call's 50
    assert s["bench.window"]["self_s"] == pytest.approx(40e-6)


def test_counters_sum_over_the_spans_of_the_window():
    s = reduced()["spans"]
    assert s["bridge.put"]["count"] == 2
    assert s["bridge.put"]["counters"] == {"bytes": 150, "ticks": 10}
    assert s["bridge.dispatch"]["counters"] == {"traced": 1}  # lo, hi identify, not count
    assert s["bridge.run_chunks"]["counters"] == {}


def test_device_clock_is_shifted_onto_the_host_clock():
    ev = json.loads(json.dumps(EVENTS))
    for key in ("ops", "modules"):
        ev["devices"]["0"][key] = [[n, s - 1000 * US, d] for n, s, d in ev["devices"]["0"][key]]
    assert trace.summarize(ev)["clock_shift_s"] == pytest.approx(1000e-6)
    r = reduced(ev, 1000e-6)
    assert r["spans"]["bridge.put"]["idle_s"] == pytest.approx(18e-6)
    assert r["dispatch_margin_s"] == pytest.approx(3e-6)


def test_dispatch_margin():
    # the scans start 3 and 27 us after the dispatches that launched them
    assert reduced()["dispatch_margin_s"] == pytest.approx(3e-6)
    ev = json.loads(json.dumps(EVENTS))
    ev["devices"]["0"]["modules"].pop()
    assert reduced(ev)["dispatch_margin_s"] is None  # the runs do not pair up


def test_put_metrics_per_tick():
    ctx = {"spans": reduced(), "ticks": 10}
    assert common.read_metric("put_ms.paper", ctx) == pytest.approx(25e-3 / 10)
    assert common.read_metric("put_mb.paper", ctx) == pytest.approx(150e-6 / 10)
    assert common.read_metric("put_idle_ms.paper", ctx) == pytest.approx(18e-3 / 10)


def test_a_program_without_spans_reads_nothing():
    """A trace of a program that writes no ``bridge.*`` span (the recorded
    one predates them): nothing to read, and no error."""
    assert spans.extract(os.path.join(DATA, "tiny.xplane.pb")) == []
    with open(os.path.join(DATA, "tiny_events.json")) as f:
        events = json.load(f)
    r = spans.reduce([], events, trace.summarize(events)["clock_shift_s"])
    assert not any(k.startswith("bridge.") for k in r["spans"])
    assert r["dispatch_margin_s"] is None
    for m in spans.PUT_METRICS:
        assert common.read_metric(m, {"spans": r, "ticks": 6}) is None
        assert common.read_metric(m, {"ticks": 6}) is None


def test_recorded_traces_summarize_as_before():
    """`bench.trace.summarize` of the recorded tiny and mesh traces, exactly
    as the reduction gave them before the program's spans and scopes were
    added (``summaries.json``); the new scope readers find nothing there."""
    with open(os.path.join(DATA, "summaries.json")) as f:
        want = json.load(f)
    with open(os.path.join(DATA, "tiny_events.json")) as f:
        tiny = json.load(f)
    with open(os.path.join(DATA, "tiny_hlo.txt")) as f:
        got = {"tiny": trace.summarize(tiny, [f.read()])}
    with open(os.path.join(DATA, "mesh_events.json")) as f:
        mesh = json.load(f)
    with gzip.open(os.path.join(DATA, "mesh_hlo.txt.gz"), "rt") as f:
        got["mesh"] = trace.summarize(mesh, [f.read()])
    assert json.loads(json.dumps(got)) == want
    for m in ("layout_ms.train", "screen_ms.mesh", "grad_ms.mesh"):
        assert common.read_metric(m, {"trace": got["mesh"], "ticks": 1}) is None

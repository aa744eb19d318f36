"""The trace reduction (`bench.trace`): exact on a hand-made trace, and
sound on small traces recorded on a TPU v5e (``bench/tests/data/``)."""
import gzip
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HLO = """HloModule jit_step, entry_computation_layout={()->()}

ENTRY %main () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(), kind=kLoop, calls=%c1, metadata={op_name="jit(step)/while/body/bridge.grad/dot_general"}
  %sort.2 = f32[8]{0} sort(), dimensions={0}, metadata={op_name="jit(step)/while/body/bridge.screen/sort"}
  %while = f32[8]{0} while(), condition=%cond, body=%body, metadata={op_name="jit(step)/while"}
  ROOT %all-gather.3 = f32[32]{0} all-gather(), dimensions={0}
}
"""

US = 1000  # the hand-made trace counts in microseconds (the events are in ns)
EVENTS = {
    "devices": {"0": {
        "modules": [["jit_step", 10 * US, 105 * US]],
        "ops": [["while", 10 * US, 80 * US], ["fusion.1", 10 * US, 30 * US],
                ["sort.2", 40 * US, 30 * US], ["all-gather.3", 95 * US, 20 * US]],
        "async": [],
    }},
    "host": [["bench.window", 0, 115 * US], ["bench.call", 0, 5 * US],
             ["bench.wait", 100 * US, 15 * US]],
}


def test_hand_made_trace():
    s = trace.summarize(EVENTS, [HLO])
    assert s["window_s"] == pytest.approx(115e-6)
    assert s["clock_shift_s"] == 0.0  # the last op ends where the window does
    assert s["busy_s"] == pytest.approx(100e-6)  # [10, 90] and [95, 115]
    assert s["scope_s"]["bridge.grad"] == pytest.approx(30e-6)
    assert s["scope_s"]["bridge.screen"] == pytest.approx(30e-6)
    # the while's self time is its span less its body's ops
    assert s["scope_s"]["while"] == pytest.approx(80e-6)  # 20 of its own + 60 nested
    assert dict(s["device_ops"])["jit(step)/while"] == pytest.approx(20e-6)
    assert s["collective_s"] == pytest.approx(20e-6)
    assert s["collective_exposed_s"] == pytest.approx(20e-6)
    assert s["idle_gaps"] == [["bench.call", pytest.approx(10e-6)],
                              ["no host span", pytest.approx(5e-6)]]


def test_device_clock_is_aligned_to_the_window_end():
    ev = json.loads(json.dumps(EVENTS))
    for key in ("ops", "modules"):
        ev["devices"]["0"][key] = [[n, s - 1000 * US, d] for n, s, d in ev["devices"]["0"][key]]
    s = trace.summarize(ev, [HLO])
    assert s["clock_shift_s"] == pytest.approx(1000e-6)
    assert s["busy_s"] == pytest.approx(100e-6)
    assert s["scope_s"]["bridge.screen"] == pytest.approx(30e-6)


def test_collective_hidden_by_compute():
    ev = json.loads(json.dumps(EVENTS))
    ev["devices"]["0"]["async"] = [["all-gather-start.4", 20 * US, 40 * US]]  # under fusion.1, sort.2
    s = trace.summarize(ev, [HLO])
    assert s["collective_s"] == pytest.approx(60e-6)
    assert s["collective_exposed_s"] == pytest.approx(20e-6)


# The shape of a v5e mesh step's gather, as the chip's compiler emits it
# (trimmed from the compiled HLO of the mesh cell's step): an async start,
# a matmul fusion that carries the transfer, the done, and a plain
# all-gather.
HLO_ASYNC = """HloModule jit_train_step, entry_computation_layout={()->()}

%fused_computation.685 (param_0.2157: f32[1,1,2560]) -> (f32[4,1,2560], u32[]) {
  %param_0.2157 = f32[1,1,2560]{2,1,0:T(1,128)} parameter(0)
  %all-gather.63 = f32[4,1,2560]{2,1,0:T(1,128)} all-gather(%param_0.2157), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(train_step)/shard_map/all_gather"}
  ROOT %custom-call.5 = (f32[4,1,2560]{2,1,0:T(1,128)}, u32[]{:S(2)}) custom-call(%all-gather.63), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.554 (param_0.2161: f32[1,1,2560], param_9.92: f32[2,1024,2560]) -> (f32[2,1024,1024], f32[4,1,2560]) {
  %param_9.92 = f32[2,1024,2560]{2,1,0:T(8,128)} parameter(1)
  %convolution.91 = f32[2,1024,1024]{1,2,0:T(8,128)} convolution(%param_9.92, %param_9.92), dim_labels=b0f_0io->b0f
  %param_0.2161 = f32[1,1,2560]{2,1,0:T(1,128)} parameter(0)
  %all-gather.65 = f32[4,1,2560]{2,1,0:T(1,128)} all-gather(%param_0.2161), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %tuple.268 = (f32[2,1024,1024]{1,2,0:T(8,128)}, f32[4,1,2560]{2,1,0:T(1,128)}) tuple(%convolution.91, %all-gather.65)
}

%fused_computation.705 (param_0.2265: f32[1,1,2560]) -> f32[4,1,2560] {
  %param_0.2265 = f32[1,1,2560]{2,1,0:T(1,128)} parameter(0)
  %all-gather.103 = f32[4,1,2560]{2,1,0:T(1,128)} all-gather(%param_0.2265), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %custom-call.7 = f32[4,1,2560]{2,1,0:T(1,128)} custom-call(%all-gather.103), custom_call_target="AsyncCollectiveDone"
}

%fused_computation.1 (param_0.1: f32[4,2560]) -> f32[2560] {
  %param_0.1 = f32[4,2560]{1,0} parameter(0)
  ROOT %reduce.1 = f32[2560]{0} reduce(%param_0.1), dimensions={0}
}

ENTRY %main.115_spmd (param: f32[1,1,2560]) -> f32[2560] {
  %param = f32[1,1,2560]{2,1,0:T(1,128)} parameter(0)
  %async-collective-start = (f32[4,1,2560]{2,1,0:T(1,128)}, u32[]{:S(2)}) fusion(%param), kind=kCustom, calls=%fused_computation.685
  %fusion.554 = (f32[2,1024,1024]{1,2,0:T(8,128)}, f32[4,1,2560]{2,1,0:T(1,128)}) fusion(%param, %param), kind=kOutput, calls=%async_collective_fusion.554, metadata={op_name="jit(train_step)/vmap(jvp())/dot_general"}
  %async-collective-done = f32[4,1,2560]{2,1,0:T(1,128)} fusion(%param), kind=kCustom, calls=%fused_computation.705, metadata={op_name="jit(train_step)/shard_map/all_gather"}
  %all-gather.148 = f32[4,1,1,2560]{3,2,1,0:T(1,128)} all-gather(%param), channel_id=6, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(train_step)/shard_map/all_gather"}
  ROOT %fusion.1 = f32[2560]{0} fusion(%all-gather.148), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/shard_map/reduce_min"}
}
"""


def test_collective_kinds_from_hlo():
    kinds = trace.collective_kinds(HLO_ASYNC)
    assert kinds["async-collective-start"] == "collective"
    assert kinds["async-collective-done"] == "collective"
    assert kinds["all-gather.148"] == "collective"
    assert kinds["fusion.554"] == "overlap"  # a matmul that carries the gather
    assert kinds["fusion.1"] == ""


def test_async_collective_counts_and_hides():
    ops = [["async-collective-start", 10 * US, 10 * US], ["fusion.554", 20 * US, 20 * US],
           ["async-collective-done", 40 * US, 5 * US], ["all-gather.148", 45 * US, 8 * US],
           ["fusion.1", 53 * US, 30 * US]]
    ev = {"devices": {"0": {"modules": [["jit_train_step", 10 * US, 73 * US]], "ops": ops,
                            "async": []}},
          "host": [["bench.window", 0, 83 * US]]}
    s = trace.summarize(ev, [HLO_ASYNC])
    # start, the carrying matmul, done and the plain gather: 10 + 20 + 5 + 8
    assert s["collective_s"] == pytest.approx(43e-6)
    # the matmul hides its 20 us; the rest waits on the transfer
    assert s["collective_exposed_s"] == pytest.approx(23e-6)
    assert s["scope_s"]["all_gather"] == pytest.approx(13e-6)  # done + plain gather


def test_self_time_counts_every_ns_once():
    s = trace.summarize(EVENTS, [HLO])
    assert sum(v for _, v in s["device_ops"]) == pytest.approx(s["busy_s"])


def test_instruction_and_module_names():
    assert trace.instr_name("%fusion.28 = (f32[]) fusion(f32[] %p), kind=kOutput") == "fusion.28"
    assert trace.module_name("jit_scan_chunk(16381531416752665127)") == "jit_scan_chunk"
    mod, names = trace.op_names(HLO)
    assert mod == "jit_step" and names["sort.2"] == "jit(step)/while/body/bridge.screen/sort"
    assert "all-gather.3" not in names  # no metadata: reported by module and opcode


def test_recorded_tpu_trace():
    """A scan of three ticks (bridge.grad / bridge.screen / bridge.apply
    scopes), called twice, recorded on a TPU v5e with the host spans a run
    writes."""
    with open(os.path.join(DATA, "tiny_events.json")) as f:
        events = json.load(f)
    assert trace.extract(os.path.join(DATA, "tiny.xplane.pb")) == events
    with open(os.path.join(DATA, "tiny_hlo.txt")) as f:
        s = trace.summarize(events, [f.read()])
    assert 0 < s["busy_s"] <= s["window_s"]
    for scope in ("bridge.grad", "bridge.screen", "bridge.apply"):
        assert s["scope_s"][scope] > 0
    # the screen's sort is the tick's largest op
    assert max(s["scope_s"][k] for k in ("bridge.grad", "bridge.screen", "bridge.apply")) \
        == s["scope_s"]["bridge.screen"]
    assert sum(v for _, v in s["device_ops"]) <= s["busy_s"] * (1 + 1e-9)
    assert {g[0] for g in s["idle_gaps"]} <= {"bench.call", "bench.block", "no host span"}


def test_recorded_mesh_trace():
    """One step of the four-chip mesh cell, devices 0 and 1, recorded on a
    2x2 v5e host (``mesh_events.json``), with the HLO lines of the ops it
    ran and the called computations of its collectives (``mesh_hlo.txt.gz``)."""
    with open(os.path.join(DATA, "mesh_events.json")) as f:
        events = json.load(f)
    with gzip.open(os.path.join(DATA, "mesh_hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    s = trace.summarize(events, [hlo])
    kinds = trace.collective_kinds(hlo)
    per_dev = []
    for dev in events["devices"].values():
        got = {"collective": 0.0, "overlap": 0.0}
        for name, _, d in dev["ops"]:
            if kinds.get(name):
                got[kinds[name]] += d * 1e-9
        per_dev.append(got)
    fullest = max(per_dev, key=lambda g: g["collective"] + g["overlap"])
    # the gather's own ops (async starts and dones, plain all-gathers) wait
    # on the transfer; the matmuls that carry it hide it
    assert fullest["collective"] > 0 and fullest["overlap"] > 0
    assert s["collective_exposed_s"] == pytest.approx(fullest["collective"], rel=1e-6)
    assert s["collective_s"] == pytest.approx(fullest["collective"] + fullest["overlap"],
                                              rel=1e-6)
    # the step as read on the chip: 36.7 ms with a transfer in flight, 18.6 exposed
    assert s["collective_s"] == pytest.approx(0.036686209, rel=1e-6)
    assert s["collective_exposed_s"] == pytest.approx(0.018607064, rel=1e-6)
    # an op-name prefix alone sees only the plain all-gathers
    plain = max(sum(d for n, _, d in dev["ops"] if n.startswith("all-gather"))
                for dev in events["devices"].values()) * 1e-9
    assert plain < 0.6 * s["collective_exposed_s"]

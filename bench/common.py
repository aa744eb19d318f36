"""What every run shares: the files a cell is made of, the chip guard, the
table of peaks, the compile clock, and the metric readers."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_files(name: str) -> tuple[dict, dict, dict, dict]:
    """(workload entry, configuration, traffic, limits) of a cell, by name."""
    bm = benchmark()
    work = {w["name"]: w for w in bm["workloads"]}.get(name)
    if work is None:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(w['name'] for w in bm['workloads'])}")
    conf = {c["name"]: c for c in bm["configs"]}[work["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", f"{work['traffic']}.json"))
    limits = load_json(os.path.join(BENCH, "limits", f"{name}.json"))
    return work, cfg, traffic, limits


def cell_metrics(name: str, trace: bool) -> list[dict]:
    """The metrics a run of the cell reports: its end-to-end metrics, or with
    ``trace`` its per-layer ones."""
    bm = benchmark()
    e2e = [m for m in bm["end_to_end"] if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in moved else [])]


def read_metric(name: str, ctx: dict):
    """Run ``bench/metrics/<name>.py``'s ``read(ctx)``; None when it finds
    nothing to read."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in bench/peaks.json; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def require_accelerator(chips: int):
    """The devices of a run: an accelerator with at least ``chips`` of them,
    or exit non-zero before anything is printed."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("bench: JAX found no accelerator; nothing ran")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


class CompileClock:
    """Seconds JAX spends lowering and compiling, and how many lowerings and
    backend compiles ran, while the context is open (`jax.monitoring`).  A
    copy of ``chip_smoke.CompileClock`` that also counts lowerings, since a
    program that is traced again inside the window is lowered again even when
    the compile cache then serves it."""

    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax

        self.seconds, self.lowerings, self.compiles = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def _listen(self, event, duration, **_):
        if event in (self._LOWER, self._COMPILE):
            self.seconds += duration
            self.lowerings += event == self._LOWER
            self.compiles += event == self._COMPILE

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)

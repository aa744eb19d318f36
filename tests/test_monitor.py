"""repro.obs.monitor + perfetto + manifest: the out-of-process telemetry
consumers (ISSUE 9).

Everything here drives the artifacts a run leaves on disk — including the
killed-run case where only a partial ``metrics.jsonl`` and the start-bracket
manifest exist — through the monitor's incremental tailer and HTTP API, the
Chrome-trace exporter (golden-checked entry by entry), and the manifest
write/merge/read round-trip.
"""
import json
import os
import threading
import urllib.request

import pytest

from repro.obs import read_manifest, write_manifest
from repro.obs import monitor as obs_monitor
from repro.obs import perfetto as obs_perfetto
from repro.obs import report as obs_report
from repro.obs.metrics import AlertRules
from repro.obs.monitor import RunTail, serve


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


@pytest.fixture()
def run_dir(tmp_path):
    """A 'killed run': start-bracket manifest, a few metric rows (the last
    one divergent), chunk events — but no run.end and no ended manifest."""
    d = str(tmp_path / "run")
    write_manifest(d, kind="unit-test", config={"steps": 4, "rule": "median"})
    _write_jsonl(os.path.join(d, "metrics.jsonl"), [
        {"tag": "train", "wall": 0.1, "tick": 0, "loss": 2.0,
         "consensus_dist": 0.5, "nonfinite": 0.0},
        {"tag": "train", "wall": 0.2, "tick": 1, "loss": 1.5,
         "consensus_dist": 0.4, "nonfinite": 0.0},
        {"tag": "train", "wall": 0.3, "tick": 2, "loss": None,
         "consensus_dist": None, "nonfinite": 1.0},
    ])
    _write_jsonl(os.path.join(d, "events.jsonl"), [
        {"tag": "run.start", "wall": 0.0, "time": 1.0},
        {"tag": "train.chunk", "wall": 0.25, "time": 1.2, "train_tag": "train",
         "lo": 0, "hi": 2},
    ])
    return d


# ---------------------------------------------------------------------------
# manifest round-trip
# ---------------------------------------------------------------------------


def test_manifest_round_trip_and_merge(tmp_path):
    d = str(tmp_path)
    write_manifest(d, kind="train", config={"lr": 0.1, "steps": 8})
    m = read_manifest(d)
    assert m["kind"] == "train"
    assert m["config"] == {"lr": 0.1, "steps": 8}
    assert len(m["config_digest"]) == 16
    assert "python" in m["environment"]
    assert "ended" not in m
    # the end bracket MERGES: kind/config survive, extras land on top
    write_manifest(d, extra={"ended": True, "wall_s": 3.5})
    m2 = read_manifest(d)
    assert m2["kind"] == "train"
    assert m2["config_digest"] == m["config_digest"]
    assert m2["ended"] is True and m2["wall_s"] == 3.5
    # no leftover temp file from the atomic write
    assert os.listdir(d) == ["manifest.json"]


def test_manifest_digest_is_config_stable(tmp_path):
    a = write_manifest(str(tmp_path / "a"), config={"x": 1, "y": [2, 3]})
    b = write_manifest(str(tmp_path / "b"), config={"y": [2, 3], "x": 1})
    da = read_manifest(str(tmp_path / "a"))["config_digest"]
    db = read_manifest(str(tmp_path / "b"))["config_digest"]
    assert a != b and da == db  # key order does not change the digest
    write_manifest(str(tmp_path / "b"), config={"x": 1, "y": [2, 4]})
    assert read_manifest(str(tmp_path / "b"))["config_digest"] != da


def test_manifest_absent_or_torn_reads_none(tmp_path):
    assert read_manifest(str(tmp_path)) is None
    with open(tmp_path / "manifest.json", "w") as f:
        f.write('{"kind": "tr')  # torn write from a killed process
    assert read_manifest(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# the tailer
# ---------------------------------------------------------------------------


def test_runtail_snapshot_of_killed_run(run_dir):
    tail = RunTail(run_dir)
    snap = tail.snapshot()
    assert snap["rows"] == 3 and snap["events"] == 2
    assert snap["tags"] == ["train"]
    assert snap["manifest"]["kind"] == "unit-test"
    assert snap["last"]["tick"] == 2
    # the monitor-side engine re-derives alerts, so the killed run (whose
    # writer never emitted obs.alert) still surfaces its divergence
    assert [a["kind"] for a in snap["alerts"]] == ["divergence"]
    assert snap["alerts"][0]["tag"] == "train"


def test_runtail_incremental_and_torn_line(run_dir):
    tail = RunTail(run_dir)
    tail.refresh()
    assert len(tail.rows) == 3
    mpath = os.path.join(run_dir, "metrics.jsonl")
    with open(mpath, "a") as f:  # a live writer mid-line: no newline yet
        f.write('{"tag": "train", "wall": 0.4, "tick": 3, "lo')
    tail.refresh()
    assert len(tail.rows) == 3  # torn tail is NOT consumed
    with open(mpath, "a") as f:
        f.write('ss": 1.0}\n')
    tail.refresh()
    assert len(tail.rows) == 4 and tail.rows[-1]["loss"] == 1.0
    assert tail.metrics_since(1, "train")[0]["tick"] == 2
    assert tail.metrics_since(1, "other") == []
    events, total = tail.events_since(1)
    assert total == 2 and [e["tag"] for e in events] == ["train.chunk"]


def test_runtail_dedupes_writer_emitted_alerts(run_dir):
    """obs.alert events from the run's own writer merge with (not duplicate)
    the monitor-side engine's alerts, keyed by (stream, kind)."""
    with open(os.path.join(run_dir, "events.jsonl"), "a") as f:
        f.write(json.dumps({"tag": "obs.alert", "wall": 0.35, "time": 1.3,
                            "kind": "divergence", "stream": "train",
                            "tick": 2}) + "\n")
        f.write(json.dumps({"tag": "obs.alert", "wall": 0.36, "time": 1.3,
                            "kind": "wire_budget", "stream": "train",
                            "tick": 2, "budget": 10.0}) + "\n")
    tail = RunTail(run_dir)
    tail.refresh()
    kinds = sorted(a["kind"] for a in tail.alerts)
    assert kinds == ["divergence", "wire_budget"]  # divergence only once
    wb = next(a for a in tail.alerts if a["kind"] == "wire_budget")
    assert wb["tag"] == "train" and "stream" not in wb


# ---------------------------------------------------------------------------
# the HTTP API
# ---------------------------------------------------------------------------


@pytest.fixture()
def server(run_dir):
    srv = serve(run_dir, port=0, rules=AlertRules())
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv
    srv.shutdown()
    srv.server_close()


def _get(server, path):
    port = server.server_address[1]
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.headers.get_content_type(), r.read()


def test_monitor_http_smoke(server):
    code, ctype, body = _get(server, "/")
    assert code == 200 and ctype == "text/html"
    html = body.decode()
    assert "<svg" in html or "lineChart" in html  # the inline dashboard
    code, ctype, body = _get(server, "/api/run")
    snap = json.loads(body)
    assert code == 200 and snap["rows"] == 3
    assert snap["manifest"]["kind"] == "unit-test"
    code, _, body = _get(server, "/api/metrics?after=0&tag=train")
    rows = json.loads(body)["rows"]
    assert code == 200 and [r["tick"] for r in rows] == [1, 2]
    code, _, body = _get(server, "/api/events?offset=1")
    ev = json.loads(body)
    assert code == 200 and ev["total"] == 2 and len(ev["events"]) == 1


def test_monitor_http_unknown_path_404(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server, "/api/nope")
    assert ei.value.code == 404


def test_monitor_once_cli(run_dir, capsys):
    assert obs_monitor.main([run_dir, "--once"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["rows"] == 3 and snap["run_dir"] == run_dir


# ---------------------------------------------------------------------------
# perfetto export (golden)
# ---------------------------------------------------------------------------


def test_chrome_trace_golden():
    """Entry-by-entry check of the Trace Event Format conversion."""
    events = [
        {"tag": "run.start", "wall": 0.0, "time": 1.0, "steps": 4},
        {"tag": "train.chunk", "wall": 0.5, "time": 1.5, "train_tag": "train",
         "lo": 0, "hi": 2},
        {"tag": "obs.alert", "wall": 0.6, "time": 1.6, "kind": "divergence",
         "stream": "train", "tick": 2},
        {"tag": "run.end", "wall": 0.7, "time": 1.7, "steps": 4, "wall_s": 0.4},
    ]
    rows = [{"tag": "train", "wall": 0.45, "tick": 1, "loss": 1.5,
             "stale_p50": None}]
    trace = obs_perfetto.chrome_trace(events, rows, {"kind": "unit-test"})
    assert trace["displayTimeUnit"] == "ms"
    assert trace["otherData"] == {"kind": "unit-test"}
    te = trace["traceEvents"]
    # metadata: process + one thread_name per track, in tid order
    metas = [e for e in te if e["ph"] == "M"]
    assert metas[0]["args"] == {"name": "repro"}
    assert [(m["tid"], m["args"]["name"]) for m in metas[1:]] == [
        (1, "run"), (2, "train/train"), (3, "alerts")]
    # a record with a duration becomes an X slice ENDING at its wall time
    x = next(e for e in te if e["ph"] == "X")
    assert x["name"] == "run.end" and x["tid"] == 1
    assert x["ts"] == pytest.approx((0.7 - 0.4) * 1e6)
    assert x["dur"] == pytest.approx(0.4 * 1e6)
    # a chunk record is an instant on its stream's track; run.start and the
    # alert are instants on their own tracks
    instants = [e for e in te if e["ph"] == "i"]
    assert {e["name"] for e in instants} == {"run.start", "train.chunk", "obs.alert"}
    chunk = next(e for e in instants if e["name"] == "train.chunk")
    assert chunk["tid"] == 2 and chunk["ts"] == pytest.approx(0.5 * 1e6)
    assert chunk["args"]["lo"] == 0 and chunk["args"]["hi"] == 2
    # the metric row is one counter per non-null, non-tick column
    counters = [e for e in te if e["ph"] == "C"]
    assert [(c["name"], c["args"]) for c in counters] == [
        ("train/loss", {"loss": 1.5})]
    assert counters[0]["ts"] == pytest.approx(0.45 * 1e6)
    # the non-meta stream is globally ts-sorted
    ts = [e["ts"] for e in te if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_perfetto_export_of_killed_run(run_dir):
    path = obs_perfetto.export(run_dir)
    assert path == os.path.join(run_dir, "trace.json")
    with open(path) as f:
        trace = json.load(f)
    assert trace["otherData"]["kind"] == "unit-test"
    names = {e["name"] for e in trace["traceEvents"]}
    assert "train.chunk" in names and "train/loss" in names


def test_perfetto_export_metrics_only(tmp_path):
    """No events.jsonl at all (a run killed before its first chunk event)
    still renders as a counter-only trace."""
    d = str(tmp_path)
    _write_jsonl(os.path.join(d, "metrics.jsonl"),
                 [{"tag": "train", "wall": 0.1, "tick": 0, "loss": 2.0}])
    with open(obs_perfetto.export(d)) as f:
        trace = json.load(f)
    assert [e["name"] for e in trace["traceEvents"] if e["ph"] == "C"] == [
        "train/loss"]


def test_perfetto_cli(run_dir, tmp_path, capsys):
    out = str(tmp_path / "t.json")
    assert obs_perfetto.main([run_dir, "--out", out]) == 0
    assert "trace events" in capsys.readouterr().out
    assert json.load(open(out))["traceEvents"]


# ---------------------------------------------------------------------------
# the report CLI renders killed-run artifacts
# ---------------------------------------------------------------------------


def test_report_renders_manifest_and_live_streams(run_dir):
    from repro.obs import read_events
    from repro.obs.metrics import read_metrics

    text = obs_report.render(
        None, read_events(os.path.join(run_dir, "events.jsonl")),
        manifest=read_manifest(run_dir),
        metrics_rows=read_metrics(os.path.join(run_dir, "metrics.jsonl")))
    assert "unit-test" in text          # manifest kind
    assert "train" in text              # the live stream's tag
    assert "nonfinite" in text.lower() or "1" in text

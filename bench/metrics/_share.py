"""Arithmetic the metric readers share.  Each reader is
``bench/metrics/<metric>.py`` with ``read(ctx) -> number | None``; ``ctx``
is filled by ``bench/run.py``: ``setup_s``, ``compile_s``, ``window_s``
(host seconds of the window that ran), ``ticks`` and ``tokens`` in it,
``peak_bytes``, ``trace`` (`bench.trace.summarize` of a ``--trace 1`` run,
else None), ``work`` (`bench.work.tick_counts`), ``peak`` (the device's row
of ``bench/peaks.json``), ``chips``, ``config``, ``traffic``."""
from __future__ import annotations


def scope_ms_per_tick(ctx, scope: str):
    """Device self time of the ops under a named scope, per tick (ms)."""
    tr = ctx["trace"]
    if tr is None or ctx["ticks"] == 0 or not tr["scope_s"].get(scope):
        return None
    return tr["scope_s"][scope] / ctx["ticks"] * 1e3


def screen_roofline(ctx, scope: str):
    """Least time for the screen's bytes over its device time (%)."""
    ms = scope_ms_per_tick(ctx, scope)
    if ms is None:
        return None
    return 100.0 * ctx["work"]["screen_bytes"] / ctx["peak"]["hbm_bytes_per_s"] / (ms * 1e-3)


def collective_ms_per_tick(ctx, key: str):
    tr = ctx["trace"]
    if tr is None or ctx["ticks"] == 0 or not tr["collective_s"]:
        return None
    return tr[key] / ctx["ticks"] * 1e3

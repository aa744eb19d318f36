"""Device time per tick of the ops under ``bridge.screen`` (flat tick)."""
from bench.metrics._share import scope_ms_per_tick


def read(ctx):
    return scope_ms_per_tick(ctx, "bridge.screen")

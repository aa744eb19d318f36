"""Device idle time per tick that falls inside the program's ``bridge.put``
spans, with no span nested inside them covering it (`bench.spans`)."""
from bench.spans import per_tick


def read(ctx):
    return per_tick(ctx, "bridge.put", "idle_s", 1e3)

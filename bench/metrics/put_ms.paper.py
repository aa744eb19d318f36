"""Host self time per tick of the program's ``bridge.put`` spans: the
host-to-device put of a chunk's batches in `run_chunks` (`bench.spans`)."""
from bench.spans import per_tick


def read(ctx):
    return per_tick(ctx, "bridge.put", "self_s", 1e3)

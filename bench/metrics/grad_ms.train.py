"""Device time per tick of the model step (``stream.grad``)."""
from bench.metrics._share import scope_ms_per_tick


def read(ctx):
    return scope_ms_per_tick(ctx, "stream.grad")

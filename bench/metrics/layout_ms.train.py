"""Device time per tick of the streaming tick's reshapes of the parameter
and gradient leaves to and from their [M, s] views (``stream.layout``)."""
from bench.metrics._share import scope_ms_per_tick


def read(ctx):
    return scope_ms_per_tick(ctx, "stream.layout")

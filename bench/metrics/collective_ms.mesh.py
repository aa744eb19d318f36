"""Device time of collective ops per tick on the device with the most."""
from bench.metrics._share import collective_ms_per_tick


def read(ctx):
    return collective_ms_per_tick(ctx, "collective_s")

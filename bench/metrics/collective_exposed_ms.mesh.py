"""The part of the collective time per tick in which no compute op runs
on that device."""
from bench.metrics._share import collective_ms_per_tick


def read(ctx):
    return collective_ms_per_tick(ctx, "collective_exposed_s")

"""Device time per tick of the mesh path's screen of the gathered rows
(``mesh.screen``), averaged over the chips."""
from bench.metrics._share import scope_ms_per_tick


def read(ctx):
    return scope_ms_per_tick(ctx, "mesh.screen")

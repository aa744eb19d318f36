"""Device time per tick of the mesh path's model step, forward and backward
(``mesh.grad``), averaged over the chips."""
from bench.metrics._share import scope_ms_per_tick


def read(ctx):
    return scope_ms_per_tick(ctx, "mesh.grad")

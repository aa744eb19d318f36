"""Host bytes put on the device per tick (MB, 1e6 bytes): the ``bytes``
counter of the program's ``bridge.put`` spans (`bench.spans`)."""
from bench.spans import per_tick


def read(ctx):
    return per_tick(ctx, "bridge.put", "bytes", 1e-6)

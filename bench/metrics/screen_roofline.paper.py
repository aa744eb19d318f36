"""The flat screen's share of its memory roofline: 2 M d float32 bytes
at the HBM bandwidth over its device time per tick (%)."""
from bench.metrics._share import screen_roofline


def read(ctx):
    return screen_roofline(ctx, "bridge.screen")

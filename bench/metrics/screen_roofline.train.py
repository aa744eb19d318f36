"""The streamed screen's share of its memory roofline (%)."""
from bench.metrics._share import screen_roofline


def read(ctx):
    return screen_roofline(ctx, "stream.screen")

"""Peak device memory of the fullest chip, in GB (1e9 bytes), read after
the window and before the reference runs: arrays at their peak plus the
temporaries' reserved region (`bench.run.peak_bytes`)."""


def read(ctx):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] > 0 else None

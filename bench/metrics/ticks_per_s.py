"""BRIDGE ticks completed in the window over the window's seconds."""


def read(ctx):
    return ctx["ticks"] / ctx["window_s"]

"""Tokens of all nodes trained in the window over the window's seconds."""


def read(ctx):
    return ctx["tokens"] / ctx["window_s"]

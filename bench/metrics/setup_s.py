"""Set-up seconds: process start to the window's start, compile included."""


def read(ctx):
    return ctx["setup_s"]

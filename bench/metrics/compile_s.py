"""Seconds of lowering and backend compiling during set-up
(`bench.common.CompileClock`): what a warm compile cache leaves."""


def read(ctx):
    return ctx["compile_s"] if ctx["compile_s"] > 0 else None

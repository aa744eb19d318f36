"""Model FLOP/s utilisation (%): forward + backward FLOPs per token (no
recompute) times tokens per second of the traced window, over the chips'
bf16 peak."""


def read(ctx):
    if ctx["trace"] is None or ctx["tokens"] == 0:
        return None
    flops = ctx["work"]["flops"] / ctx["work"]["tokens"] * ctx["tokens"]
    return 100.0 * flops / ctx["window_s"] / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])

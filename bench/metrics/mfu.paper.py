"""The whole tick's share of the chip's roofline (%): the larger of the
required FLOPs over the bf16 peak and the required bytes over the HBM
bandwidth (`bench.work.tick_counts`), over the traced window's seconds per
tick."""


def read(ctx):
    if ctx["trace"] is None or ctx["ticks"] == 0:
        return None
    w, p = ctx["work"], ctx["peak"]
    least = max(w["flops"] / p["bf16_flops_per_s"], w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / (ctx["window_s"] / ctx["ticks"])

"""The fullest expert's tokens over the mean expert's, over the
window's flushed steps and sparse layers, as a ratio of sums:
``SchedulerStats.moe_load_max`` (the fullest expert's tokens, summed
over steps and layers) over ``moe_pairs / experts held a layer`` (the
mean expert's, summed likewise). 1 is even routing; the grouped matmul
waits for its fullest group. None where the server keeps no such
counters."""


def read(ctx):
    if not hasattr(ctx.window.stats_close, "moe_pairs"):
        return None
    pairs = ctx.stats_delta("moe_pairs")
    lo, hi = ctx.cfg.get("experts_held") or (0, ctx.cfg.get("num_experts", 0))
    if not pairs or hi <= lo:
        return None
    return ctx.stats_delta("moe_load_max") * (hi - lo) / pairs

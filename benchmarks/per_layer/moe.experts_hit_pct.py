"""Share of the experts held that were given a real token, over the
window's flushed steps and sparse layers:
``SchedulerStats.moe_experts_hit / moe_experts_held`` (the step returns
its tokens per expert with its sampled tokens). It says how much of the
expert weights a step has to read. None where the server keeps no such
counters (a program before PR 34, a family with no routed layer)."""


def read(ctx):
    if not hasattr(ctx.window.stats_close, "moe_experts_held"):
        return None
    held = ctx.stats_delta("moe_experts_held")
    return 100.0 * ctx.stats_delta("moe_experts_hit") / held if held else None

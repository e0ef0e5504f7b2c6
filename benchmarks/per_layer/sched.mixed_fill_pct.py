"""Share of a mixed step's token matrix (slots x chunk) that held a
real prompt token, over the window's mixed steps:
``SchedulerStats.prefill_tokens / (mixed_steps x slots x mixed_chunk)``."""


def read(ctx):
    steps = ctx.stats_delta("mixed_steps")
    sc = ctx.engine_serving
    if not steps:
        return None
    return 100.0 * ctx.stats_delta("prefill_tokens") / (
        steps * sc.max_requests_per_batch * sc.mixed_chunk)

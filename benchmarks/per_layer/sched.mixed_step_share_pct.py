"""Share of the window's scheduler steps that were mixed steps (one
whole chunk wide because some row prefilled):
``SchedulerStats.mixed_steps / steps``. Every decoding row pays a mixed
step's device time for one token, so this is what lifts the inter-token
gap's tail above the decode step's time."""


def read(ctx):
    steps = ctx.stats_delta("steps")
    return 100.0 * ctx.stats_delta("mixed_steps") / steps if steps else None

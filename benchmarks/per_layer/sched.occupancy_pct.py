"""Mean share of the slots that held an active row, per scheduler step
of the window: ``SchedulerStats.occupancy_sum / steps``."""


def read(ctx):
    steps = ctx.stats_delta("steps")
    return 100.0 * ctx.stats_delta("occupancy_sum") / steps if steps else None

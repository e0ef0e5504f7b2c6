"""Requests the scheduler preempted (pages taken back, prompt computed
again) inside the window: ``SchedulerStats.preemptions``."""


def read(ctx):
    return float(ctx.stats_delta("preemptions"))

"""Median host time of ``ff.step.reserve`` in the traced window:
``RequestManager._reserve_active_pages`` growing every active slot's
page table for the step (a flush it forces on a full pool is the
scheduler's, and is taken off). None where the trace holds no such span
(a program before PR 27, an unpaged layout)."""
from benchmarks.harness import reduce, stats


def read(ctx):
    t = ctx.trace
    if not hasattr(t, "spans"):
        return None
    flushes = reduce.union(t.spans("ff.step.flush"))
    return stats.median([
        ((e - s) - reduce.overlap([(s, e)], flushes)) / 1e6
        for s, e in t.spans("ff.step.reserve")])

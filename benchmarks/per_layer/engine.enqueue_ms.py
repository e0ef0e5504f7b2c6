"""Median host time of ``ff.step.dispatch`` in the traced window: the
engine's ``run_decode`` / ``run_mixed`` call, which is the
``device_put`` of the step's host arrays and the call of the jitted
step program (the runtime enqueues it on a thread of its own). None
where the trace holds no such span (a program before PR 27)."""
from benchmarks.harness import stats


def read(ctx):
    t = ctx.trace
    if not hasattr(t, "spans"):
        return None
    return stats.median(
        [(e - s) / 1e6 for s, e in t.spans("ff.step.dispatch")])

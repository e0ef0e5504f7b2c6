"""The scheduler's own host time in one turn of the loop: per
``bench.step`` span of the traced window, the union of the
``ff.step.admit``, ``ff.step.build`` and ``ff.step.flush`` spans inside
it (a flush nested in an admission is counted once) less the
``ff.step.flush_wait`` inside those (the host waiting on the device is
the engine's); median. With ``cache.reserve_ms`` and
``engine.enqueue_ms`` it splits what ``engine.dispatch_ms.*`` sums.
None where the trace holds no such span (a program before PR 27)."""
from benchmarks.harness import reduce, stats

OWN = ("ff.step.admit", "ff.step.build", "ff.step.flush")


def read(ctx):
    t = ctx.trace
    if not hasattr(t, "spans"):
        return None
    own = reduce.union([iv for name in OWN for iv in t.spans(name)])
    if not own:
        return None
    waits = reduce.union(t.spans("ff.step.flush_wait"))
    return stats.median([
        (reduce.overlap([turn], own) - reduce.overlap([turn], waits)) / 1e6
        for turn in t.spans("bench.step")])

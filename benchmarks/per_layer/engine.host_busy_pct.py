"""Share of the traced window in which the host works rather than
waits on the device: 100 x (1 - the ``ff.step.flush_wait`` spans'
total over the window). The flush's blocking fetch is where a host that
keeps ahead of the device spends its spare time, so at 100 the host has
none left and the device starts to idle. None where the trace holds no
such span (a program before PR 27)."""
from benchmarks.harness import reduce


def read(ctx):
    t = ctx.trace
    if not hasattr(t, "spans"):
        return None
    waits = reduce.union(t.spans("ff.step.flush_wait"))
    if not waits:
        return None
    return 100.0 * (1.0 - reduce.total(waits) / (t.hi - t.lo))

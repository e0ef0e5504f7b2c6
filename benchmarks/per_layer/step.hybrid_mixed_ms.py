"""Median device time of the C=chunk mixed step program of a hybrid
configuration in the traced sub-window, found by NAME: the ``XLA
Modules`` events called ``jit_ff_step_c<chunk>(...)`` (the engine names
every step program from its key; a program with several Pallas kernels
has no one kernel whose extent could say which program it is). None
where no module carries the name."""
from benchmarks.harness import stats


def step_ms(ctx):
    t = ctx.trace
    name = f"jit_ff_step_c{ctx.engine_serving.mixed_chunk}("
    return stats.median([dur / 1e6 for n, s, dur, _ in getattr(t, "modules", ())
                         if n.startswith(name) and t.lo <= s and s + dur <= t.hi])


def read(ctx):
    return step_ms(ctx)

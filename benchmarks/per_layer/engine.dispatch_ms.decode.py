"""Median host time of a ``bench.step`` span that dispatched the
C=1 decode step, less the runtime's wait events inside it in the same
trace. None where the trace holds no wait event to subtract: a span
that is mostly waiting for the device is the device step again."""


def read(ctx):
    return ctx.trace.dispatch_ms(1)

"""Median device time of the C=1 decode step program in the traced
sub-window: the ``XLA Modules`` events whose one custom call has that
chunk extent."""


def read(ctx):
    return ctx.trace.program_ms(1)

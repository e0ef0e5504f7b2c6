"""Median device time of the C=chunk mixed step program in the traced
sub-window: the ``XLA Modules`` events whose one custom call has that
chunk extent."""


def read(ctx):
    return ctx.trace.program_ms(ctx.engine_serving.mixed_chunk)

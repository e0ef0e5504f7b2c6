"""Least time by shapes of ONE call of the ragged paged attention
kernel in the C=chunk mixed step (``counts/ragged_kernel.py``) over the
median device time of that call: the program's only custom call, one
per layer."""
from benchmarks.harness import roofline


def read(ctx):
    ms = ctx.trace.kernel_call_ms(ctx.engine_serving.mixed_chunk)
    return roofline.share(ctx, "ragged_kernel", "mixed", ms and ms / 1e3,
                          "kernel.ragged.mixed")

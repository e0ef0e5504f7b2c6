"""Least time by shapes of ONE call of the ragged paged attention
kernel in the C=1 decode step (``counts/ragged_kernel.py``) over the
median device time of that call: the program's only custom call, one
per layer."""
from benchmarks.harness import roofline


def read(ctx):
    ms = ctx.trace.kernel_call_ms(1)
    return roofline.share(ctx, "ragged_kernel", "decode", ms and ms / 1e3,
                          "kernel.ragged.decode")

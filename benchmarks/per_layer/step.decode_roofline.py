"""Least time by shapes of the C=1 decode step (``counts/step.py``:
weights once, the cache lines of the real contexts, the FLOPs of the
real tokens; the larger of the two bounds) over ``step.decode_ms``."""
from benchmarks.harness import roofline


def read(ctx):
    ms = ctx.trace.program_ms(1)
    return roofline.share(ctx, "step", "decode", ms and ms / 1e3,
                          "step.decode")

"""Least time by shapes of the C=chunk mixed step (``counts/step.py``:
weights once, the cache lines of the real contexts, the FLOPs of the
real tokens; the larger of the two bounds) over ``step.mixed_ms``."""
from benchmarks.harness import roofline


def read(ctx):
    ms = ctx.trace.program_ms(ctx.engine_serving.mixed_chunk)
    return roofline.share(ctx, "step", "mixed", ms and ms / 1e3,
                          "step.mixed")

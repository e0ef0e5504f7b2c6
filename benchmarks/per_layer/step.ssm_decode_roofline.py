"""Least time by shapes of the C=1 decode step of a configuration with
Mamba-2 layers (``counts/granite_hybrid_step.py``: every weight once
with the tied matrix once, the state-space and convolution states of
the rows that step read and written once, K/V lines of the attention
layers only, the FLOPs of real tokens) over ``step.decode_ms``."""
from benchmarks.harness import roofline


def read(ctx):
    ms = ctx.trace.program_ms(1)
    return roofline.share(ctx, "granite_hybrid_step", "decode", ms and ms / 1e3,
                          "step.ssm.decode")

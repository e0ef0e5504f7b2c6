"""Least time by shapes of the C=1 decode step of a configuration with
routed experts and conv layers (``counts/lfm2_step.py``: the weights of
the experts hit and the others once, K/V lines of the attention layers
only, conv states once a row, the FLOPs of real tokens and routed
pairs) over ``step.decode_ms``."""
from benchmarks.harness import roofline


def read(ctx):
    ms = ctx.trace.program_ms(1)
    return roofline.share(ctx, "lfm2_step", "decode", ms and ms / 1e3,
                          "step.moe.decode")

"""Least time by shapes of the C=1 decode step of a configuration with
Gated DeltaNet layers and a held range of routed experts
(``counts/qwen3_next_step.py``: the weights of the held experts hit and
every other weight once, the recurrent and convolution states of the
rows that step read and written once, K/V lines of the full layers
only, the FLOPs of real tokens and routed pairs) over
``step.decode_ms``."""
from benchmarks.harness import roofline


def read(ctx):
    ms = ctx.trace.program_ms(1)
    return roofline.share(ctx, "qwen3_next_step", "decode", ms and ms / 1e3,
                          "step.gdn_moe.decode")

"""Least time by shapes of a latent-attention configuration's C=chunk
mixed step (``counts/deepseek_step.py``: the weights of the experts hit
and the others once, one compressed line a token and layer, the FLOPs
of real tokens with attention in its absorbed form) over
``step.mla_mixed_ms``, the mean over the executed widths."""
from benchmarks.harness import roofline, spec


def read(ctx):
    ms = spec.load_module("per_layer", "step.mla_mixed_ms").step_ms(ctx)
    return roofline.share(ctx, "deepseek_step", "mixed", ms and ms / 1e3,
                          "step.mla.mixed")

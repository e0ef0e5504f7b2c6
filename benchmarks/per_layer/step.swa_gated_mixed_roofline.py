"""Least time by shapes of a Laguna configuration's C=chunk mixed step
(``counts/laguna_step.py``: the weights of the experts hit and the
others once, attention's by the layer's kind, the K/V of the pages a
real query may see by the layer's kind, the FLOPs of real tokens) over
``step.swa_gated_mixed_ms``, the mean over the executed widths: the
share of the whole step."""
from benchmarks.harness import roofline, spec


def read(ctx):
    ms = spec.load_module("per_layer", "step.swa_gated_mixed_ms").step_ms(ctx)
    return roofline.share(ctx, "laguna_step", "mixed", ms and ms / 1e3,
                          "step.swa_gated.mixed")

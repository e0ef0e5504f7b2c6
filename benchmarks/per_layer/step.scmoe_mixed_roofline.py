"""Least time by shapes of a LongCat-Flash configuration's C=chunk
mixed step (``counts/longcat_step.py``: the weights of the experts hit
and the others once, two compressed lines a token and layer, the FLOPs
of real tokens with attention absorbed and the identity pairs costing
nothing) over ``step.scmoe_mixed_ms``, the mean over the executed
widths: the share of the whole step."""
from benchmarks.harness import roofline, spec


def read(ctx):
    ms = spec.load_module("per_layer", "step.scmoe_mixed_ms").step_ms(ctx)
    return roofline.share(ctx, "longcat_step", "mixed", ms and ms / 1e3,
                          "step.scmoe.mixed")

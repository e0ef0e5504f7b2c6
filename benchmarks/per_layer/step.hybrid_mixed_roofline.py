"""Least time by shapes of a hybrid configuration's C=chunk mixed step
(``counts/hybrid_step.py``: weights once, the lightning states read and
written once a row, K/V lines and compressed keys of the chosen blocks
only, the FLOPs of real tokens) over ``step.hybrid_mixed_ms``."""
from benchmarks.harness import roofline, spec


def read(ctx):
    ms = spec.load_module("per_layer", "step.hybrid_mixed_ms").step_ms(ctx)
    return roofline.share(ctx, "hybrid_step", "mixed", ms and ms / 1e3,
                          "step.hybrid.mixed")

"""Least time of ONE call of the latent paged attention kernel in the
C=chunk mixed step at a LongCat-Flash configuration's keys
(``counts/longcat_mla_kernel.py``: 64 heads as published) over the
median device time of that call, found by the kernel's NAME as
``kernel.mla_roofline.mixed`` finds it (``ff_mla_paged_c<chunk>``; a
layer has two such calls, of the same shapes). None where no operation
carries the name."""
from benchmarks.harness import roofline, spec


def read(ctx):
    ms = spec.load_module("per_layer", "kernel.mla_roofline.mixed").call_ms(ctx)
    return roofline.share(ctx, "longcat_mla_kernel", "mixed", ms and ms / 1e3,
                          "kernel.latent.mixed")

"""Least time of ONE call of the gated delta rule's kernel in the C=1
decode step where a key head serves a group of value heads
(``counts/gdn_grouped_recur_kernel.py``) over the median device time of
that call, found by the kernel's NAME, ``ff_gdn_recur_c1``
(``per_layer/kernel.gdn_recur_roofline.decode.py``'s ``call_ms``). None
where no operation carries the name (a program whose recurrence is
XLA's)."""
from benchmarks.harness import roofline, spec


def read(ctx):
    call_ms = spec.load_module("per_layer", "kernel.gdn_recur_roofline.decode").call_ms
    ms = call_ms(ctx)
    return roofline.share(ctx, "gdn_grouped_recur_kernel", "decode",
                          ms and ms / 1e3, "kernel.gdn_grouped_recur.decode")

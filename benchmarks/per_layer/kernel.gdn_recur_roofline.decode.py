"""Least time of ONE call of the gated delta rule's kernel in the C=1
decode step (``counts/gdn_recur_kernel.py``) over the median device
time of that call, found by the kernel's NAME: the ``XLA Ops`` events
whose HLO instruction is called ``ff_gdn_recur_c1``. None where no
operation carries the name (a program whose recurrence is XLA's)."""
from benchmarks.harness import roofline, stats

NAME = "ff_gdn_recur_c1"


def call_ms(ctx):
    t = ctx.trace
    return stats.median([
        dur / 1e6 for n, _, _, kernel, s, dur in getattr(t, "ops", ())
        if kernel and t.lo <= s < t.hi and n.split(".")[0] == NAME])


def read(ctx):
    ms = call_ms(ctx)
    return roofline.share(ctx, "gdn_recur_kernel", "decode", ms and ms / 1e3,
                          "kernel.gdn_recur.decode")

"""Least time of ONE call of the latent paged attention kernel in the
C=chunk mixed step (``counts/mla_kernel.py``) over the median device
time of that call, found by the kernel's NAME: the ``XLA Ops`` events
whose HLO instruction is called ``ff_mla_paged_c<chunk>``. None where
no operation carries the name (a program without the kernel)."""
from benchmarks.harness import roofline, stats


def call_ms(ctx):
    t = ctx.trace
    name = f"ff_mla_paged_c{ctx.engine_serving.mixed_chunk}"
    return stats.median([
        dur / 1e6 for n, _, _, kernel, s, dur in getattr(t, "ops", ())
        if kernel and t.lo <= s < t.hi and n.split(".")[0] == name])


def read(ctx):
    ms = call_ms(ctx)
    return roofline.share(ctx, "mla_kernel", "mixed", ms and ms / 1e3,
                          "kernel.mla.mixed")

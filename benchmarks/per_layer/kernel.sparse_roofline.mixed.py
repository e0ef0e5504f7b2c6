"""Least time of ONE call of the block-sparse paged attention kernel in
the C=chunk mixed step (``counts/sparse_kernel.py``) over the median
device time of that call, found by the kernel's NAME: the ``XLA Ops``
events whose HLO instruction is called ``ff_sparse_paged_c<chunk>``.
None where no operation carries the name."""
from benchmarks.harness import roofline, stats


def read(ctx):
    t = ctx.trace
    name = f"ff_sparse_paged_c{ctx.engine_serving.mixed_chunk}"
    ms = stats.median([
        dur / 1e6 for n, _, _, kernel, s, dur in getattr(t, "ops", ())
        if kernel and t.lo <= s < t.hi and n.split(".")[0] == name])
    return roofline.share(ctx, "sparse_kernel", "mixed", ms and ms / 1e3,
                          "kernel.sparse.mixed")

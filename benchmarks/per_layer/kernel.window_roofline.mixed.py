"""Least time of ONE call of the ragged paged attention kernel by a
WINDOW layer in the C=chunk mixed step (``counts/window_kernel.py``:
the keys a real query may see under the window and the pages that hold
them) over the median device time of that call, found by the kernel's
NAME: the ``XLA Ops`` events whose HLO instruction is called
``ff_ragged_paged_c<chunk>_win``. None where no operation carries the
name (a program without window layers of their own class of page)."""
from benchmarks.harness import roofline, stats


def call_ms(ctx, suffix="_win"):
    t = ctx.trace
    name = f"ff_ragged_paged_c{ctx.engine_serving.mixed_chunk}{suffix}"
    return stats.median([
        dur / 1e6 for n, _, _, kernel, s, dur in getattr(t, "ops", ())
        if kernel and t.lo <= s < t.hi and n.split(".")[0] == name])


def read(ctx):
    ms = call_ms(ctx)
    return roofline.share(ctx, "window_kernel", "mixed", ms and ms / 1e3,
                          "kernel.window.mixed")

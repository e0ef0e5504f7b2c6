"""Least time of ONE call of the ragged paged attention kernel by a
WINDOW layer (64 query heads at the published widths) in the C=chunk
mixed step of a configuration whose head count goes by the kind of
layer (``counts/window_kind_kernel.py``: the keys a real query may see
under the window and the pages that hold them) over the median device
time of that call, found by the kernel's NAME
(``ff_ragged_paged_c<chunk>_win``; ``kernel.window_roofline.mixed``'s
``call_ms``). None where no operation carries the name."""
from benchmarks.harness import roofline, spec


def read(ctx):
    ms = spec.load_module("per_layer", "kernel.window_roofline.mixed").call_ms(ctx)
    return roofline.share(ctx, "window_kind_kernel", "mixed", ms and ms / 1e3,
                          "kernel.window_kind.mixed")

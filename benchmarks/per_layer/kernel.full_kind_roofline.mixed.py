"""Least time of ONE call of the ragged paged attention kernel by a
FULL layer (48 query heads at the published widths, counted at 48 and
not at the 64 the call is padded to) in the C=chunk mixed step of a
configuration whose head count goes by the kind of layer
(``counts/full_kind_kernel.py``: every key of a row's context) over the
median device time of that call, by NAME: ``ff_ragged_paged_c<chunk>``
in a program that also holds ``ff_ragged_paged_c<chunk>_win``. None
where no operation carries the second name."""
from benchmarks.harness import roofline, spec


def read(ctx):
    window = spec.load_module("per_layer", "kernel.window_roofline.mixed")
    if window.call_ms(ctx) is None:
        return None
    ms = window.call_ms(ctx, suffix="")
    return roofline.share(ctx, "full_kind_kernel", "mixed", ms and ms / 1e3,
                          "kernel.full_kind.mixed")

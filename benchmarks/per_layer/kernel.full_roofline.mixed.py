"""Least time of ONE call of the ragged paged attention kernel by a
FULL layer of a family that also has window layers, in the C=chunk
mixed step (``counts/full_kernel.py``: every key of a row's context)
over the median device time of that call, by NAME:
``ff_ragged_paged_c<chunk>`` in a program that also holds
``ff_ragged_paged_c<chunk>_win``. None where no operation carries the
second name (a family with one kind of attention layer reads
``kernel.ragged_roofline.mixed``)."""
from benchmarks.harness import roofline, spec


def read(ctx):
    window = spec.load_module("per_layer", "kernel.window_roofline.mixed")
    if window.call_ms(ctx) is None:
        return None
    ms = window.call_ms(ctx, suffix="")
    return roofline.share(ctx, "full_kernel", "mixed", ms and ms / 1e3,
                          "kernel.full.mixed")

"""Operations and bytes ONE call of the ragged paged attention kernel
needs in a SmallThinker step, by the KIND of layer that makes it, for
the tokens that exist: the keys a real query may see (its own context,
in a window layer at most ``sliding_window_size`` of it) and the pages
that hold them, read once a row; queries read, outputs written (with
each K/V head's group of query heads padded to 8, as the call moves
them: 32 heads of 128 where the model has 28; no operations are counted
for the padding). It
reads the same work whatever implements the call: the pages a window
layer's call walks or skips are the implementation's, not the count's.
bf16 (2 bytes). ``count``: a window layer's call (``ff_ragged_paged_
c<C>_win``); ``count_full``: a full layer's (``ff_ragged_paged_c<C>``;
``counts/full_kernel.py`` hands it on under the name a reader gives
``roofline.share``). ``mix`` as in ``counts/step.py``."""
from .smallthinker_sizes import rows_of, seen, sizes

BYTES = 2


def call(cfg, mix, windowed):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    pairs, lines = seen(s, rows_of(mix), windowed)
    flops = 4.0 * s["H"] * s["d"] * pairs            # q k^T and p v
    nbytes = BYTES * (s["kv_line"] * lines + 2 * tokens * s["H_call"] * s["d"])
    return flops, nbytes


def count(cfg, mix):
    return call(cfg, mix, True)


def count_full(cfg, mix):
    return call(cfg, mix, False)

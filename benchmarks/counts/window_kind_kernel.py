"""Operations and bytes ONE call of the ragged paged attention kernel
needs in a step of a configuration whose query-head count goes by the
KIND of layer (``counts/laguna_sizes.py``), for the tokens that exist:
the keys a real query may see (its own context, in a window layer at
most ``sliding_window`` of it) and the pages that hold them, read once
a row; queries read and outputs written at the layer's REAL head count
(a K/V head's group padded to 8 for the call moves zeros and computes
nothing the model asks for: no work). It reads the same work whatever
implements the call. bf16 (2 bytes). ``count``: a window layer's call
(``ff_ragged_paged_c<C>_win``); ``count_full``: a full layer's
(``ff_ragged_paged_c<C>``; ``counts/full_kind_kernel.py`` hands it on).
``mix`` as in ``counts/step.py``."""
from .laguna_sizes import rows_of, seen, sizes

BYTES = 2


def call(cfg, mix, windowed):
    s = sizes(cfg)
    H = s["H_win"] if windowed else s["H_full"]
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    pairs, lines = seen(s, rows_of(mix), windowed)
    flops = 4.0 * H * s["d"] * pairs                 # q k^T and p v
    nbytes = BYTES * (s["kv_line"] * lines + 2 * tokens * H * s["d"])
    return flops, nbytes


def count(cfg, mix):
    return call(cfg, mix, True)


def count_full(cfg, mix):
    return call(cfg, mix, False)

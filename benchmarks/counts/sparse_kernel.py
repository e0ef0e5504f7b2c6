"""Operations and bytes ONE call of the block-sparse paged attention
kernel (``ff_sparse_paged_c<C>``: one sparse layer's attention of one
step in which some row is above ``dense_len``) needs for the tokens
that exist: every real query attends the chosen blocks' keys (all keys
up to ``dense_len``), each row's attended lines are read once, queries
are read and outputs written. bf16. ``mix`` as in ``counts/step.py``."""
from .hybrid_sizes import attended, mean_attended, rows_of, sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    dec_ctx, prompt = rows_of(mix)
    keys = mix["decode_rows"] * attended(s, dec_ctx) + mix["prefill_tokens"] * mean_attended(s, prompt)
    flops = 4.0 * s["H"] * s["d"] * keys
    lines = mix["decode_rows"] * attended(s, dec_ctx) + mix["prefill_rows"] * attended(s, prompt / 2.0)
    nbytes = BYTES * (s["kv_line"] * lines + 2 * tokens * s["H"] * s["d"])
    return flops, nbytes

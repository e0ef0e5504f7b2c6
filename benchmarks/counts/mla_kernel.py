"""Operations and bytes ONE call of the latent paged attention kernel
(``ff_mla_paged_c<C>``: one layer's attention of one step) needs for
the rows that exist, causal: every real query of every head scores the
lines it may see over the line's whole width (``[c | kr]``) and sums
their ``c``, so 2 H (line + rank) operations a (query, line) pair —
``mix`` counts the pairs from the rows' live contexts
(``roofline.step_mix``: a decoding row sees its context, a prefilling
row's tokens half its prompt on average), nothing for padding columns
or pages past a row's last query; each row's lines are read once, the
absorbed queries are read and the outputs written. bf16 (2 bytes)."""
from .deepseek_sizes import sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    pairs = mix["decode_ctx"] + mix["prefill_tok_ctx"]
    flops = 2.0 * s["H"] * (s["line"] + s["rank"]) * pairs
    lines = mix["decode_ctx"] + mix["prefill_row_ctx"]
    nbytes = BYTES * (s["line"] * lines + tokens * s["H"] * (s["line"] + s["rank"]))
    return flops, nbytes

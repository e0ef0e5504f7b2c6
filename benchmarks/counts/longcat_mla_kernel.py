"""Operations and bytes ONE call of the latent paged attention kernel
(``ff_mla_paged_c<C>``: one attention sublayer of one step) needs for
the rows that exist, causal, at a LongCat-Flash configuration's keys:
``counts/mla_kernel.py``'s rule (2 H (line + rank) operations a
(query, line) pair; each row's lines read once, the absorbed queries
read and the outputs written), which reads its sizes through
``deepseek_sizes`` and so cannot read this file's. bf16 (2 bytes)."""
from .longcat_sizes import sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    pairs = mix["decode_ctx"] + mix["prefill_tok_ctx"]
    flops = 2.0 * s["H"] * (s["line"] + s["rank"]) * pairs
    lines = mix["decode_ctx"] + mix["prefill_row_ctx"]
    nbytes = BYTES * (s["line"] * lines + tokens * s["H"] * (s["line"] + s["rank"]))
    return flops, nbytes

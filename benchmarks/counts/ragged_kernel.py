"""Operations and bytes ONE call of the ragged paged attention kernel
(one layer's attention of one step) needs for the tokens that exist:
every real query attends the keys of its own context, each row's
cached lines are read once, queries are read and outputs written.
bf16 (2 bytes). ``mix`` as in ``counts/step.py``."""
from .sizes import sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    keys = mix["decode_ctx"] + mix["prefill_tok_ctx"]
    flops = 4.0 * s["H"] * s["dk"] * keys
    lines_read = mix["decode_ctx"] + mix["prefill_row_ctx"]
    nbytes = BYTES * (s["kv_line"] * lines_read + 2 * tokens * s["H"] * s["dk"])
    return flops, nbytes

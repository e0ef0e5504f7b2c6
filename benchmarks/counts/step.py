"""Operations and bytes one step program needs for the tokens that
exist — the decode step (C=1) and the mixed step (C=chunk) alike, since
the algorithm is the same: padded rows and padded chunk positions are
not work. bf16 weights and cache (2 bytes).

  mix: decode_rows, decode_ctx (sum of their context lengths),
       prefill_tokens, prefill_rows, prefill_row_ctx (sum over the
       prefilling rows of the lines each has cached), prefill_tok_ctx
       (sum over the prefill tokens of the keys each attends)
"""
from .sizes import ffn_params_per_token, ffn_params_read, sizes

BYTES = 2


def count(cfg, mix):
    """(flops, bytes) of one step over ``mix``."""
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    keys = mix["decode_ctx"] + mix["prefill_tok_ctx"]
    flops = 2.0 * tokens * s["L"] * (s["attn"] + ffn_params_per_token(s))
    flops += 4.0 * s["H"] * s["dk"] * keys * s["L"]   # q k^T and p v
    flops += 2.0 * rows * s["D"] * s["V"]             # one logits row per row
    weights = s["L"] * (s["attn"] + ffn_params_read(s, tokens)) + s["D"] * s["V"]
    lines_read = mix["decode_ctx"] + mix["prefill_row_ctx"]
    nbytes = BYTES * (weights + s["L"] * s["kv_line"] * (lines_read + tokens)
                      + tokens * s["D"])
    return flops, nbytes

"""Operations and bytes one step program of an LFM2-MoE configuration
needs for the tokens that exist (``mix`` as in ``counts/step.py``): the
weights of the experts HIT (``lfm2_sizes.experts_hit``: expected under
even routing) and every other weight once, the tied head's table once;
K/V lines of the ATTENTION layers only; each conv layer's state read
and written once a row; the FLOPs of real tokens: the mixers, the dense
FFN, the router and the routed (token, expert) pairs, attention over
what each token attends, the conv taps, one logits row a row. bf16
weights, cache and state (2 bytes)."""
from .lfm2_sizes import experts_hit, pairs_held, sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    keys = mix["decode_ctx"] + mix["prefill_tok_ctx"]
    per_token = (s["n_conv"] * s["conv_mixer"] + s["n_attn"] * s["attn_mixer"]
                 + s["n_dense"] * s["dense_ffn"] + s["n_sparse"] * s["router"])
    flops = 2.0 * tokens * per_token
    flops += 2.0 * s["n_sparse"] * pairs_held(s, tokens) * s["expert"]
    flops += 4.0 * s["H"] * s["d"] * keys * s["n_attn"]      # q k^T and p v
    flops += 2.0 * s["taps"] * s["D"] * tokens * s["n_conv"]
    flops += 2.0 * rows * s["D"] * s["V"]
    weights = (per_token + s["n_sparse"] * experts_hit(s, tokens) * s["expert"]
               + s["D"] * s["V"])
    lines = mix["decode_ctx"] + mix["prefill_row_ctx"] + tokens
    nbytes = BYTES * (weights + s["n_attn"] * s["kv_line"] * lines
                      + 2 * rows * s["n_conv"] * s["conv_state"]
                      + tokens * s["D"])
    return flops, nbytes

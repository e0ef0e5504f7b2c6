"""Operations and bytes one step program of a Qwen3-Next configuration
needs for the tokens that exist (``mix`` as in ``counts/step.py``): the
weights of the experts HIT of those held (``qwen3_next_sizes.experts_hit``:
expected under even routing) and every other weight once (mixers,
routers, shared experts), the untied head's table once and the
embedding rows of the tokens; K/V lines of the FULL layers only, at the
traced window's contexts; each recurrent layer's float32 state and bf16
convolution state read and written once a row that steps; the FLOPs of
real tokens: the mixers', routers' and shared experts' matmuls, the
routed (token, expert) pairs that fall on the experts held, attention
over what each token attends, the gated delta rule
(``qwen3_next_sizes.delta_rule_flops``), one logits row a row. bf16
weights and cache (2 bytes)."""
from .qwen3_next_sizes import (
    delta_rule_flops,
    experts_hit,
    pairs_held,
    sizes,
    state_bytes,
)

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    keys = mix["decode_ctx"] + mix["prefill_tok_ctx"]
    per_token = (s["n_gdn"] * s["gdn_mixer"] + s["n_attn"] * s["attn_mixer"]
                 + s["n_layers"] * (s["router"] + s["shared"]))
    flops = 2.0 * tokens * per_token
    flops += 2.0 * s["n_layers"] * pairs_held(s, tokens) * s["expert"]
    flops += 4.0 * s["H"] * s["d"] * keys * s["n_attn"]      # q k^T and p v
    flops += s["n_gdn"] * delta_rule_flops(
        s, mix["decode_rows"], mix["prefill_tokens"])
    flops += 2.0 * rows * s["D"] * s["V"]
    weights = (per_token + s["n_layers"] * experts_hit(s, tokens) * s["expert"]
               + s["D"] * s["V"])
    lines = mix["decode_ctx"] + mix["prefill_row_ctx"] + tokens
    nbytes = BYTES * (weights + tokens * s["D"]
                      + s["n_attn"] * s["kv_line"] * lines)
    nbytes += s["n_gdn"] * state_bytes(s, rows)
    return flops, nbytes

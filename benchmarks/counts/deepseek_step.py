"""Operations and bytes one step program of a DeepSeek-V3 configuration
needs for the tokens that exist (``mix`` as in ``counts/step.py``): the
weights of the experts HIT (``deepseek_sizes.experts_hit``: expected
under even routing) and every other weight once (each layer's latent
attention, the dense FFN, the router and the shared expert of each
sparse layer, the head); each row's cached LINES once a layer, one
compressed line a token and not K and V a head; the FLOPs of real
tokens: the projections, the absorbed queries and outputs, the dense
FFN, the router, the shared expert and the routed (token, expert)
pairs that fall on the experts held, attention over what each token
attends in its absorbed form (``counts/mla_kernel.py``), one logits row
a row. bf16 weights and pool (2 bytes)."""
from .deepseek_sizes import experts_hit, pairs_held, sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    pairs = mix["decode_ctx"] + mix["prefill_tok_ctx"]
    per_token = (s["layers"] * s["mla"] + s["n_dense"] * s["dense_ffn"]
                 + s["n_sparse"] * (s["router"] + s["shared"]))
    flops = 2.0 * tokens * (per_token + s["layers"] * s["absorb"])
    flops += 2.0 * s["n_sparse"] * pairs_held(s, tokens) * s["expert"]
    flops += 2.0 * s["H"] * (s["line"] + s["rank"]) * pairs * s["layers"]
    flops += 2.0 * rows * s["D"] * s["V"]
    weights = (per_token + s["n_sparse"] * experts_hit(s, tokens) * s["expert"]
               + s["D"] * s["V"])
    lines = mix["decode_ctx"] + mix["prefill_row_ctx"] + tokens
    nbytes = BYTES * (weights + s["layers"] * s["line"] * lines + tokens * s["D"])
    return flops, nbytes

"""Operations and bytes one step program of a LongCat-Flash
configuration needs for the tokens that exist (``mix`` as in
``counts/step.py``): the weights of the experts HIT
(``longcat_sizes.experts_hit``: expected under even routing) and every
other weight once (each layer's TWO latent attentions, its TWO dense
FFNs and its router; the head); each row's cached LINES once an
attention sublayer, two a layer, and the step's own lines written; the
FLOPs of real tokens: the projections, the absorbed queries and
outputs, the dense FFNs, the router, the routed (token, expert) pairs
that fall on the experts held (a pair on an identity output costs
nothing: one multiply a channel), attention over what each token
attends in its absorbed form (``counts/longcat_mla_kernel.py``) twice a
layer, one logits row a row. bf16 weights and pool (2 bytes)."""
from .longcat_sizes import experts_hit, pairs_held, sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    pairs = mix["decode_ctx"] + mix["prefill_tok_ctx"]
    attends = s["layers"] * s["sublayers"]
    per_token = s["layers"] * (
        s["sublayers"] * (s["mla"] + s["dense_ffn"]) + s["router"])
    flops = 2.0 * tokens * (per_token + attends * s["absorb"])
    flops += 2.0 * s["layers"] * pairs_held(s, tokens) * s["expert"]
    flops += 2.0 * s["H"] * (s["line"] + s["rank"]) * pairs * attends
    flops += 2.0 * rows * s["D"] * s["V"]
    weights = (per_token + s["layers"] * experts_hit(s, tokens) * s["expert"]
               + s["D"] * s["V"])
    lines = mix["decode_ctx"] + mix["prefill_row_ctx"] + tokens
    nbytes = BYTES * (weights + attends * s["line"] * lines + tokens * s["D"])
    return flops, nbytes

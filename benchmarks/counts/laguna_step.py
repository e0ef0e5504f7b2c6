"""Operations and bytes one step program of a Laguna configuration
needs for the tokens that exist (``mix`` as in ``counts/step.py``): the
weights of the experts HIT (``laguna_sizes.experts_hit``: expected
under even routing) and every other weight once (attention by the
layer's KIND: 48 or 64 query heads and their gate; the dense FFN; the
router and the shared expert of each sparse layer; the untied head);
the K/V lines of the pages that hold what a real query may see, a
layer, by its kind (a window layer: a window's worth,
``smallthinker_sizes.seen``), and the step's own lines written; the
FLOPs of real tokens: projections, the router, the shared expert, the
routed (token, expert) pairs, attention over what each token sees at
its layer's REAL head count (the heads a call is padded by are no
work), one logits row a row. bf16 weights and cache (2 bytes)."""
from .laguna_sizes import experts_hit, pairs_held, rows_of, seen, sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    by_row = rows_of(mix)
    pairs_w, lines_w = seen(s, by_row, True)
    pairs_f, lines_f = seen(s, by_row, False)
    per_token = (s["n_full"] * s["attn_full"] + s["n_window"] * s["attn_window"]
                 + s["n_dense"] * s["dense_ffn"]
                 + s["n_sparse"] * (s["router"] + s["shared"]))
    flops = 2.0 * tokens * per_token
    flops += 2.0 * s["n_sparse"] * pairs_held(s, tokens) * s["expert"]
    flops += 4.0 * s["d"] * (s["n_window"] * s["H_win"] * pairs_w
                             + s["n_full"] * s["H_full"] * pairs_f)
    flops += 2.0 * rows * s["D"] * s["V"]
    weights = (per_token + s["n_sparse"] * experts_hit(s, tokens) * s["expert"]
               + s["D"] * s["V"])
    lines = s["n_window"] * (lines_w + tokens) + s["n_full"] * (lines_f + tokens)
    nbytes = BYTES * (weights + s["kv_line"] * lines + tokens * s["D"])
    return flops, nbytes

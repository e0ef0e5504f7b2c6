"""Operations and bytes one step program of a SmallThinker
configuration needs for the tokens that exist (``mix`` as in
``counts/step.py``): the weights of the experts HIT
(``smallthinker_sizes.experts_hit``: expected under even routing) and
every other weight once, the untied head once; the K/V lines of the
pages that hold what a real query may see, a layer, by its kind (a
window layer: a window's worth, ``smallthinker_sizes.seen``), and the
step's own lines written; the FLOPs of real tokens: projections, the
router, the routed (token, expert) pairs, attention over what each
token sees, one logits row a row. bf16 weights and cache (2 bytes)."""
from .smallthinker_sizes import experts_hit, pairs_held, rows_of, seen, sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    by_row = rows_of(mix)
    pairs_w, lines_w = seen(s, by_row, True)
    pairs_f, lines_f = seen(s, by_row, False)
    per_token = s["n_layers"] * (s["attn"] + s["router"])
    flops = 2.0 * tokens * per_token
    flops += 2.0 * s["n_layers"] * pairs_held(s, tokens) * s["expert"]
    flops += 4.0 * s["H"] * s["d"] * (s["n_window"] * pairs_w + s["n_full"] * pairs_f)
    flops += 2.0 * rows * s["D"] * s["V"]
    weights = (per_token + s["n_layers"] * experts_hit(s, tokens) * s["expert"]
               + s["D"] * s["V"])
    lines = s["n_window"] * (lines_w + tokens) + s["n_full"] * (lines_f + tokens)
    nbytes = BYTES * (weights + s["kv_line"] * lines + tokens * s["D"])
    return flops, nbytes

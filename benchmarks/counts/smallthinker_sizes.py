"""Sizes of a SmallThinker configuration FILE (``sliding_window_layout``:
window layers beside full layers; ``moe_num_primary_experts`` ReGLU
experts, ``moe_num_active_primary_experts`` a token, a router a layer
that reads the layer's input), shared by ``counts/smallthinker_step.py``
and ``counts/window_kernel.py``. Parameter counts are matmul parameters
(norm scales are left out: under 0.01%)."""


def sizes(cfg):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or D // H
    layout = list(cfg["sliding_window_layout"])[: cfg["num_hidden_layers"]]
    E = cfg["moe_num_primary_experts"]
    lo, hi = cfg.get("experts_held") or (0, E)
    serving = cfg.get("serving", {})
    return dict(
        D=D, V=V, H=H, KV=KV, d=d, E=E, held=hi - lo,
        K=cfg["moe_num_active_primary_experts"],
        # query heads as the attention call moves them: each K/V head's
        # group padded to 8 (models/smallthinker._pad_groups; 28 -> 32)
        H_call=KV * (-(-(H // KV) // 8) * 8),
        W=int(cfg["sliding_window_size"]),
        page=int(serving.get("page_size", 128)),
        n_window=sum(1 for w in layout if w),
        n_full=sum(1 for w in layout if not w), n_layers=len(layout),
        attn=2 * D * H * d + 2 * D * KV * d,       # Wq Wo, Wk Wv
        expert=3 * D * cfg["moe_ffn_hidden_size"],
        router=D * E,
        kv_line=2 * KV * d,   # K and V values of one token, one layer
    )


def experts_hit(s, tokens):
    """Experts of one layer some token is routed to, of those held:
    held (1 - (1 - K/E)^tokens), expected under even routing."""
    return s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** max(tokens, 0.0))


def pairs_held(s, tokens):
    """(token, expert) pairs of ``tokens`` tokens that fall on the
    experts held, expected under even routing."""
    return tokens * s["K"] * s["held"] / s["E"]


def rows_of(mix):
    """``mix`` (``roofline.step_mix``) as rows: [(rows, tokens a row,
    context a row at its first token)] for the decoding rows (one token
    each at their mean context) and the prefilling rows (their share of
    the step's prompt tokens, half-way through their prompts: the mean
    over a prefill)."""
    out = []
    if mix["decode_rows"] > 0:
        out.append((mix["decode_rows"], 1.0,
                    mix["decode_ctx"] / mix["decode_rows"]))
    if mix["prefill_rows"] > 0:
        out.append((mix["prefill_rows"],
                    mix["prefill_tokens"] / mix["prefill_rows"],
                    mix["prefill_row_ctx"] / mix["prefill_rows"]))
    return out


def seen(s, rows, windowed):
    """What ONE attention call of a layer needs for ``rows``
    (:func:`rows_of`): ((query, key) pairs a real query may see, cached
    lines in the pages that hold those keys). A row of ``n`` tokens
    whose first sits at context ``c``: its token ``j`` sees ``c + j +
    1`` keys, in a window layer at most ``W``; the keys of all its
    tokens lie in lines ``max(0, c - W + 1) .. c + n - 1`` (a full
    layer: from 0), held by whole pages."""
    pairs = lines = 0.0
    for count, n, c in rows:
        if windowed:
            short = max(0.0, min(n, s["W"] - c))   # tokens that see under a window
            pairs += count * (short * (c + 1 + (short - 1) / 2.0)
                              + (n - short) * s["W"])
            first = max(0.0, c - s["W"] + 1)
        else:
            pairs += count * n * (c + (n + 1) / 2.0)
            first = 0.0
        pages = (c + n - 1) // s["page"] - first // s["page"] + 1
        lines += count * pages * s["page"]
    return pairs, lines

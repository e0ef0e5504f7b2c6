"""Sizes of an LFM2-MoE configuration FILE (``layer_types``: gated
short-convolution layers beside GQA layers; a leading dense FFN, then
``num_experts`` small experts, ``num_experts_per_tok`` a token), shared
by ``counts/lfm2_step.py`` and ``counts/moe_ffn.py``. Parameter counts
are matmul parameters (norm scales and the conv taps' L x D are left
out: under 0.01%)."""

CONV = "conv"


def sizes(cfg):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or D // H
    kinds = list(cfg["layer_types"])[: cfg["num_hidden_layers"]]
    n_dense = min(cfg["num_dense_layers"], len(kinds))
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])
    return dict(
        D=D, V=V, H=H, KV=KV, d=d, E=cfg["num_experts"], held=hi - lo,
        K=cfg["num_experts_per_tok"], taps=cfg["conv_L_cache"],
        n_conv=kinds.count(CONV), n_attn=len(kinds) - kinds.count(CONV),
        n_dense=n_dense, n_sparse=len(kinds) - n_dense,
        conv_mixer=4 * D * D,                       # W_in (D, 3 D) and W_out
        attn_mixer=2 * D * H * d + 2 * D * KV * d,  # Wq Wo, Wk Wv
        dense_ffn=3 * D * cfg["intermediate_size"],
        expert=3 * D * cfg["moe_intermediate_size"],
        router=D * cfg["num_experts"],
        kv_line=2 * KV * d,                         # K and V values of one token, one layer
        conv_state=(cfg["conv_L_cache"] - 1) * D,   # one layer's state of one row, values
    )


def experts_hit(s, tokens):
    """Experts of one layer some token is routed to, of those held:
    held (1 - (1 - K/E)^tokens), expected under even routing."""
    return s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** max(tokens, 0.0))


def pairs_held(s, tokens):
    """(token, expert) pairs of ``tokens`` tokens that fall on the
    experts held, expected under even routing."""
    return tokens * s["K"] * s["held"] / s["E"]

"""Parameter and cache sizes of a decoder configuration FILE (Hugging
Face's key names), shared by the count modules."""


def sizes(cfg):
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dk = D // H
    E = cfg.get("num_local_experts", 0)
    K = cfg.get("num_experts_per_tok", 0)
    return dict(
        D=D, F=F, H=H, KV=KV, dk=dk, E=E, K=K,
        L=cfg["num_hidden_layers"], V=cfg["vocab_size"],
        attn=D * H * dk + 2 * D * KV * dk + H * dk * D,  # per layer
        expert=3 * D * F,                                 # one GLU FFN
        kv_line=2 * KV * dk,                              # K and V values of one token, one layer
    )


def ffn_params_per_token(s):
    """Matmul parameters one token's FFN touches in one layer: the
    dense FFN, or the router and the K experts the token is routed to
    (what the algorithm needs; an all-expert einsum computes E)."""
    return s["D"] * s["E"] + s["K"] * s["expert"] if s["E"] else s["expert"]


def ffn_params_read(s, tokens):
    """FFN parameters a step over ``tokens`` tokens must read in one
    layer: all of a dense FFN; of a sparse one the router and the
    experts some token is routed to — E (1 - (1 - K/E)^tokens) expected
    under even routing."""
    if not s["E"]:
        return s["expert"]
    hit = s["E"] * (1.0 - (1.0 - s["K"] / s["E"]) ** max(tokens, 0))
    return s["D"] * s["E"] + hit * s["expert"]

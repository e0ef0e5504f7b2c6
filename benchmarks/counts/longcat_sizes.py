"""Sizes of a LongCat-Flash configuration FILE (two latent-attention
sublayers and two dense FFNs a layer; a softmax router over
``router_outputs`` outputs, of which ``zero_expert_num`` are identity
outputs that cost nothing and the others experts, ``moe_topk`` a
token, ``experts_held`` of the experts here), shared by
``counts/longcat_step.py`` and ``counts/longcat_mla_kernel.py``.
Parameter counts are matmul parameters (norm scales and the router's
offset are left out: under 0.01%)."""


def sizes(cfg):
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    rank, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, dv, ql = cfg["qk_nope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"]
    zero = cfg.get("zero_expert_num", 0)
    E = cfg.get("router_outputs", cfg["n_routed_experts"] + zero)
    lo, hi = cfg.get("experts_held") or (0, E - zero)
    return dict(
        D=D, V=V, H=H, E=E, zero=zero, held=hi - lo, K=cfg["moe_topk"],
        layers=cfg["num_layers"], sublayers=2,   # attentions, and dense FFNs, a layer
        # W_qa, W_qb, W_kva, W_kvb, W_o
        mla=(D * ql + ql * H * (nope + dr) + D * (rank + dr)
             + rank * H * (nope + dv) + H * dv * D),
        # what the absorbed form multiplies a token by beside its weights'
        # own matmuls: q_nope W_UK and (p c) W_UV, both (H, nope|dv, rank)
        absorb=H * rank * (nope + dv),
        dense_ffn=3 * D * cfg["ffn_hidden_size"],
        expert=3 * D * cfg["expert_ffn_hidden_size"],
        router=D * E,
        line=rank + dr,    # values of one token's cached line, one sublayer
        rank=rank,         # of which the values attention sums
    )


def experts_hit(s, tokens):
    """Experts of one layer some token is routed to, of those held:
    held (1 - (1 - K/E)^tokens), expected under even routing over ALL
    the router's outputs."""
    return s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** max(tokens, 0.0))


def pairs_held(s, tokens):
    """(token, expert) pairs of ``tokens`` tokens that fall on the
    experts held, expected under even routing."""
    return tokens * s["K"] * s["held"] / s["E"]

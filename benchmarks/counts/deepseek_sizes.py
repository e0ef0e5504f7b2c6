"""Sizes of a DeepSeek-V3 configuration FILE (latent attention in every
layer; ``first_k_dense_replace`` dense FFNs, then a sigmoid router over
``router_outputs`` experts, ``num_experts_per_tok`` a token, of which
``experts_held`` are here, beside ``n_shared_experts``), shared by
``counts/deepseek_step.py`` and ``counts/mla_kernel.py``. Parameter
counts are matmul parameters (norm scales and the router's offset are
left out: under 0.01%)."""


def sizes(cfg):
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    rank, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, dv, ql = cfg["qk_nope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"]
    layers = cfg["num_hidden_layers"]
    n_dense = min(cfg["first_k_dense_replace"], layers)
    E = cfg.get("router_outputs", cfg["n_routed_experts"])
    lo, hi = cfg.get("experts_held") or (0, E)
    expert = 3 * D * cfg["moe_intermediate_size"]
    return dict(
        D=D, V=V, H=H, E=E, held=hi - lo, K=cfg["num_experts_per_tok"],
        layers=layers, n_dense=n_dense, n_sparse=layers - n_dense,
        # W_qa, W_qb, W_kva, W_kvb, W_o
        mla=(D * ql + ql * H * (nope + dr) + D * (rank + dr)
             + rank * H * (nope + dv) + H * dv * D),
        # what the absorbed form multiplies a token by beside its weights'
        # own matmuls: q_nope W_UK and (p c) W_UV, both (H, nope|dv, rank)
        absorb=H * rank * (nope + dv),
        dense_ffn=3 * D * cfg["intermediate_size"],
        expert=expert, shared=expert * cfg.get("n_shared_experts", 0),
        router=D * E,
        line=rank + dr,    # values of one token's cached line, one layer
        rank=rank,         # of which the values attention sums
    )


def experts_hit(s, tokens):
    """Experts of one layer some token is routed to, of those held:
    held (1 - (1 - K/E)^tokens), expected under even routing."""
    return s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** max(tokens, 0.0))


def pairs_held(s, tokens):
    """(token, expert) pairs of ``tokens`` tokens that fall on the
    experts held, expected under even routing."""
    return tokens * s["K"] * s["held"] / s["E"]

"""Sizes of a Laguna configuration FILE (``layer_types``: window layers
beside full layers with a query-head count a KIND,
``num_attention_heads_per_layer``; a gate a head; ``mlp_layer_types``:
a leading dense FFN, then ``num_experts`` SiLU experts,
``num_experts_per_tok`` a token, beside one shared expert), shared by
``counts/laguna_step.py``, ``counts/window_kind_kernel.py``,
``counts/full_kind_kernel.py`` and ``counts/all_held_ffn.py``.
Parameter counts are matmul parameters (norm scales and the router's
offset are left out: under 0.01%). ``rows_of`` and ``seen`` are
``counts/smallthinker_sizes.py``'s: what a row sees under a window is
the same arithmetic whatever the family."""
from .smallthinker_sizes import rows_of, seen  # noqa: F401


def sizes(cfg):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    KV, d = cfg["num_key_value_heads"], cfg["head_dim"]
    n = cfg["num_hidden_layers"]
    kinds = list(cfg["layer_types"])[:n]
    heads = list(cfg["num_attention_heads_per_layer"])[:n]
    ffns = list(cfg["mlp_layer_types"])[:n]
    by_kind = {k: [h for h, t in zip(heads, kinds) if t == k]
               for k in ("full_attention", "sliding_attention")}
    H_full = (by_kind["full_attention"] or [cfg["num_attention_heads"]])[0]
    H_win = (by_kind["sliding_attention"] or [cfg["num_attention_heads"]])[0]
    E = cfg["num_experts"]
    lo, hi = cfg.get("experts_held") or (0, E)
    serving = cfg.get("serving", {})

    def attn(H):   # Wq Wo, Wk Wv, the gate a head
        return 2 * D * H * d + 2 * D * KV * d + D * H

    return dict(
        D=D, V=V, KV=KV, d=d, E=E, held=hi - lo,
        K=cfg["num_experts_per_tok"], H_full=H_full, H_win=H_win,
        W=int(cfg["sliding_window"]),
        page=int(serving.get("page_size", 128)),
        n_full=len(by_kind["full_attention"]),
        n_window=len(by_kind["sliding_attention"]), n_layers=n,
        n_dense=ffns.count("dense"), n_sparse=ffns.count("sparse"),
        attn_full=attn(H_full), attn_window=attn(H_win),
        dense_ffn=3 * D * cfg["intermediate_size"],
        expert=3 * D * cfg["moe_intermediate_size"],
        shared=3 * D * cfg.get("shared_expert_intermediate_size", 0),
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

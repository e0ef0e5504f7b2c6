"""Sizes of a Qwen3-Next configuration FILE (Gated DeltaNet layers with
fewer key heads than value heads beside gated full-attention layers, a
sparse block every layer: routed experts of which ``experts_held`` are
here, and a shared expert), shared by ``counts/qwen3_next_step.py``,
``counts/gdn_grouped_mixer.py``, ``counts/gdn_grouped_recur_kernel.py``
and ``counts/held_moe_ffn.py``. Parameter counts are matmul parameters
(norm scales, the taps' L x channels and the two per-head gate vectors
are left out: under 0.01%)."""

SUB_CHUNK = 64  # positions the chunk form solves at once


def sizes(cfg):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or D // H
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    every = cfg.get("full_attention_interval", 4)
    n = cfg["num_hidden_layers"]
    n_attn = sum((i + 1) % every == 0 for i in range(n))
    E = cfg.get("router_outputs", cfg["num_experts"])
    lo, hi = cfg.get("experts_held") or (0, E)
    channels = 2 * Hk * dk + Hv * dv             # q, k and v, convolved
    return dict(
        D=D, V=V, H=H, KV=KV, d=d, Hk=Hk, Hv=Hv, dk=dk, dv=dv,
        taps=cfg["linear_conv_kernel_dim"], channels=channels,
        n_gdn=n - n_attn, n_attn=n_attn, n_layers=n,
        E=E, held=hi - lo, K=cfg["num_experts_per_tok"],
        # W_qkvz (the convolved channels and z), W_ba, W_o
        gdn_mixer=D * (channels + Hv * dv) + D * 2 * Hv + Hv * dv * D,
        # Wq (a query and a gate a head), Wk, Wv, Wo
        attn_mixer=D * H * 2 * d + 2 * D * KV * d + H * d * D,
        router=D * E,
        shared=3 * D * cfg["shared_expert_intermediate_size"] + D,
        expert=3 * D * cfg["moe_intermediate_size"],
        kv_line=2 * KV * d,                      # K and V values of one token, one layer
        state=Hv * dk * dv,                      # one layer's state of one row, float32 values
        conv_state=(cfg["linear_conv_kernel_dim"] - 1) * channels,  # bf16 values
    )


def experts_hit(s, tokens):
    """Experts of one layer some token is routed to, of those held:
    held (1 - (1 - K/E)^tokens), expected under even routing."""
    return s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** max(tokens, 0.0))


def pairs_held(s, tokens):
    """(token, expert) pairs of ``tokens`` tokens that fall on the
    experts held, expected under even routing."""
    return tokens * s["K"] * s["held"] / s["E"]


def delta_rule_flops(s, decode_rows, prefill_tokens):
    """Operations of ONE recurrent layer's gated delta rule and its
    convolution for the tokens that exist. A row that steps one token
    takes the recurrence: the decay, ``S^T k``, the rank-one update and
    ``S^T q``, 7 dk dv a VALUE head. A prefilling row's token takes the
    chunk form at sub-chunks of c = 64: ``K S0``, ``q S0`` and the
    state's update, 6 dk dv a value head, the solve and ``(q k^T) U``
    (c dv each) a value head, and the triangles of ``k k^T`` and
    ``q k^T`` (c dk each) once a KEY head."""
    head = s["dk"] * s["dv"]
    recur = 7.0 * s["Hv"] * head
    chunk = (s["Hv"] * (6.0 * head + 2.0 * SUB_CHUNK * s["dv"])
             + s["Hk"] * 2.0 * SUB_CHUNK * s["dk"])
    taps = 2.0 * s["taps"] * s["channels"]
    return (decode_rows * recur + prefill_tokens * chunk
            + (decode_rows + prefill_tokens) * taps)


def state_bytes(s, rows):
    """Bytes ONE recurrent layer's per-slot states move for ``rows``
    rows that step: the float32 state and the bf16 convolution inputs,
    each read and written once."""
    return 2.0 * rows * (4 * s["state"] + 2 * s["conv_state"])

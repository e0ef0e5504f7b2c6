"""Sizes of an Olmo-Hybrid configuration FILE (``layer_types``: Gated
DeltaNet layers beside full-attention layers, a dense SiLU-gated FFN
every layer), shared by ``counts/olmo_hybrid_step.py`` and
``counts/gdn_mixer.py``. Parameter counts are matmul parameters (norm
scales, the taps' L x channels and the two per-head gate vectors are
left out: under 0.01%)."""

LINEAR = "linear_attention"
SUB_CHUNK = 64  # positions the chunk form solves at once


def sizes(cfg):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or D // H
    Hl = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kinds = list(cfg["layer_types"])[: cfg["num_hidden_layers"]]
    channels = Hl * (2 * dk + dv)               # q, k and v, convolved
    return dict(
        D=D, V=V, H=H, KV=KV, d=d, Hl=Hl, dk=dk, dv=dv,
        taps=cfg["linear_conv_kernel_dim"], channels=channels,
        n_gdn=kinds.count(LINEAR), n_attn=len(kinds) - kinds.count(LINEAR),
        n_layers=len(kinds),
        # W_qkv, the two gates' columns, the output gate, W_o
        gdn_mixer=D * channels + D * 2 * Hl + 2 * D * Hl * dv,
        attn_mixer=2 * D * H * d + 2 * D * KV * d,  # Wq Wo, Wk Wv
        ffn=3 * D * cfg["intermediate_size"],
        kv_line=2 * KV * d,                     # K and V values of one token, one layer
        state=Hl * dk * dv,                     # one layer's state of one row, float32 values
        conv_state=(cfg["linear_conv_kernel_dim"] - 1) * channels,  # bf16 values
    )


def delta_rule_flops(s, decode_rows, prefill_tokens):
    """Operations of ONE recurrent layer's gated delta rule and its
    convolutions for the tokens that exist. A row that steps one token
    takes the recurrence: the decay, ``S^T k``, the rank-one update and
    ``S^T q``, 7 dk dv a head. A prefilling row's token takes the chunk
    form at sub-chunks of c = 64: ``K S0``, ``q S0`` and the state's
    update, 6 dk dv a head, and inside the sub-chunk the triangles of
    ``k k^T`` and ``q k^T`` (c dk each), the solve and ``(q k^T) U``
    (c dv each)."""
    head = s["dk"] * s["dv"]
    recur = 7.0 * head
    chunk = 6.0 * head + 2.0 * SUB_CHUNK * (s["dk"] + s["dv"])
    taps = 2.0 * s["taps"] * s["channels"]
    return (s["Hl"] * (decode_rows * recur + prefill_tokens * chunk)
            + (decode_rows + prefill_tokens) * taps)


def state_bytes(s, rows):
    """Bytes ONE recurrent layer's per-slot states move for ``rows``
    rows that step: the float32 state and the bf16 convolution inputs,
    each read and written once."""
    return 2.0 * rows * (4 * s["state"] + 2 * s["conv_state"])

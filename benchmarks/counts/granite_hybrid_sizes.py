"""Sizes of a Granite 4.0-H configuration FILE (``layer_types``: Mamba-2
layers beside attention layers, a dense SiLU-gated MLP every layer, a
tied head), shared by ``counts/granite_hybrid_step.py`` and
``counts/ssm_mixer.py``. Parameter counts are matmul parameters (norm
scales, the taps' L x channels, their bias and the three per-head
vectors are left out: under 0.01%)."""

MAMBA = "mamba"


def sizes(cfg):
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or D // H
    Hs, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = Hs * P
    kinds = list(cfg["layer_types"])[: cfg["num_hidden_layers"]]
    channels = inner + 2 * N                    # xs, B and C, convolved
    return dict(
        D=D, V=V, H=H, KV=KV, d=d, Hs=Hs, P=P, N=N, inner=inner,
        taps=cfg["mamba_d_conv"], channels=channels,
        n_ssm=kinds.count(MAMBA), n_attn=len(kinds) - kinds.count(MAMBA),
        n_layers=len(kinds),
        # W_in (z, xBC and dt' side by side) and W_o
        ssm_mixer=D * (inner + channels + Hs) + inner * D,
        attn_mixer=2 * D * H * d + 2 * D * KV * d,  # Wq Wo, Wk Wv
        ffn=3 * D * cfg["shared_intermediate_size"],
        kv_line=2 * KV * d,                     # K and V values of one token, one layer
        state=Hs * P * N,                       # one layer's state of one row, float32 values
        conv_state=(cfg["mamba_d_conv"] - 1) * channels,  # bf16 values
    )


def scan_flops(s, decode_rows, prefill_tokens, chunk=128):
    """Operations of ONE mamba layer's scan and its convolution for the
    tokens that exist. A row that steps one token takes the recurrence:
    the decay, the rank-one write and the read ``S C``, 5 P N a head,
    and the skip. A prefilling row's token takes the chunk form at the
    engine's chunk of c = 128: ``C S0`` and the state's update, 4 P N
    a head; inside the chunk the triangle of ``C B^T`` (c N, once for
    all heads) and of the masked product with ``dt xs`` (c P a head),
    and the decay mask (c a head)."""
    head = s["P"] * s["N"]
    recur = 5.0 * head + 2.0 * s["P"]
    form = 4.0 * head + chunk * (s["P"] + 1.0) + 2.0 * s["P"]
    taps = 2.0 * s["taps"] * s["channels"]
    return (s["Hs"] * (decode_rows * recur + prefill_tokens * form)
            + prefill_tokens * chunk * s["N"]
            + (decode_rows + prefill_tokens) * taps)


def state_bytes(s, rows):
    """Bytes ONE mamba layer's per-slot states move for ``rows`` rows
    that step: the float32 state and the bf16 convolution inputs, each
    read and written once."""
    return 2.0 * rows * (4 * s["state"] + 2 * s["conv_state"])

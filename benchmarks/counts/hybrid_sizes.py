"""Sizes of a hybrid configuration FILE (``mixer_types``: lightning
linear-attention layers beside block-sparse attention layers), shared
by ``counts/hybrid_step.py`` and ``counts/sparse_kernel.py``."""

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


def sizes(cfg):
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, KV, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    LH, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    kinds = list(cfg["mixer_types"])[: cfg["num_hidden_layers"]]
    sp = cfg["sparse_config"]
    return dict(
        D=D, F=F, V=V, H=H, KV=KV, d=d, LH=LH, ld=ld,
        n_lightning=kinds.count(LIGHTNING), n_sparse=kinds.count(SPARSE),
        ffn=3 * D * F,
        lightning_mixer=5 * D * LH * ld,           # Wq Wk Wv Wgate Wo
        sparse_mixer=3 * D * H * d + 2 * D * KV * d,  # Wq Wgate Wo, Wk Wv
        state=LH * ld * ld,                        # one layer's state of one row, values
        kv_line=2 * KV * d,                        # K and V values of one token, one layer
        dense_len=sp["dense_len"], stride=sp["kernel_stride"],
        chosen=sp["topk"] * sp["block_size"],     # tokens a query attends above dense_len
    )


def attended(s, ctx):
    """Keys a query with ``ctx`` visible keys attends in a sparse layer."""
    return ctx if ctx <= s["dense_len"] else min(ctx, s["chosen"])


def mean_attended(s, prompt):
    """Mean over the positions of a prompt of ``prompt`` tokens of the
    keys a query attends: all of them up to ``dense_len``, the chosen
    blocks' after it."""
    if prompt <= 0:
        return 0.0
    low = min(prompt, s["dense_len"])
    return (low * (low + 1) / 2.0 + (prompt - low) * min(prompt, s["chosen"])) / prompt


def rows_of(mix):
    """(mean context of a decoding row, mean prompt of a prefilling
    row) of a ``roofline.step_mix`` (a prefilling row is taken half-way
    through its prompt there)."""
    dec = mix["decode_ctx"] / mix["decode_rows"] if mix["decode_rows"] else 0.0
    pre = 2.0 * mix["prefill_row_ctx"] / mix["prefill_rows"] if mix["prefill_rows"] else 0.0
    return dec, pre

"""Operations and bytes one step program of a HYBRID configuration
needs for the tokens that exist (``mix`` as in ``counts/step.py``):
weights once; each lightning layer's state read and written once a row
(float32) and 4 H d^2 operations a token (q S and the state's update);
each sparse layer's K/V lines of the CHOSEN blocks only (all lines up
to ``dense_len``), the compressed keys a row above it scores, and the
attention of each real token over what it attends; one logits row a
row. bf16 weights and cache (2 bytes), float32 state and compressed
keys (4)."""
from .hybrid_sizes import attended, mean_attended, rows_of, sizes


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    dec_ctx, prompt = rows_of(mix)
    layers = s["n_lightning"] + s["n_sparse"]
    matmul = (s["n_lightning"] * s["lightning_mixer"] + s["n_sparse"] * s["sparse_mixer"]
              + layers * s["ffn"])
    flops = 2.0 * tokens * matmul + 2.0 * rows * s["D"] * s["V"]
    flops += 4.0 * tokens * s["n_lightning"] * s["state"]
    keys = mix["decode_rows"] * attended(s, dec_ctx) + mix["prefill_tokens"] * mean_attended(s, prompt)
    scored = (mix["decode_rows"] * (dec_ctx > s["dense_len"]) * dec_ctx
              + mix["prefill_tokens"] * max(0.0, prompt - s["dense_len"]) / max(prompt, 1.0)
              * (prompt + s["dense_len"]) / 2.0) / s["stride"]
    flops += s["n_sparse"] * s["H"] * s["d"] * (4.0 * keys + 2.0 * scored)
    # bytes
    nbytes = 2.0 * (matmul + s["D"] * s["V"] + tokens * s["D"])
    nbytes += 2 * 4.0 * rows * s["n_lightning"] * s["state"]
    lines = (mix["decode_rows"] * attended(s, dec_ctx)
             + mix["prefill_rows"] * attended(s, prompt / 2.0) + tokens)
    entries = (mix["decode_rows"] * (dec_ctx > s["dense_len"]) * dec_ctx
               + mix["prefill_rows"] * (prompt / 2.0 > s["dense_len"]) * prompt / 2.0) / s["stride"]
    nbytes += s["n_sparse"] * (2.0 * s["kv_line"] * lines + 4.0 * s["KV"] * s["d"] * entries)
    return flops, nbytes

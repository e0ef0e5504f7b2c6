"""Operations and bytes one step program of a Granite 4.0-H
configuration needs for the tokens that exist (``mix`` as in
``counts/step.py``): every weight once, the tied embedding matrix ONCE
(the head reads all of it; the tokens' rows are part of it); K/V lines
of the ATTENTION layers only, at the traced window's contexts; each
mamba layer's float32 state and bf16 convolution state read and written
once a row that steps; the FLOPs of real tokens: the mixers' and the
MLP's matmuls, attention over what each token attends, the scan
(``granite_hybrid_sizes.scan_flops``), one logits row a row. bf16
weights and cache (2 bytes)."""
from .granite_hybrid_sizes import scan_flops, sizes, state_bytes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    keys = mix["decode_ctx"] + mix["prefill_tok_ctx"]
    per_token = (s["n_ssm"] * s["ssm_mixer"] + s["n_attn"] * s["attn_mixer"]
                 + s["n_layers"] * s["ffn"])
    flops = 2.0 * tokens * per_token
    flops += 4.0 * s["H"] * s["d"] * keys * s["n_attn"]      # q k^T and p v
    flops += s["n_ssm"] * scan_flops(
        s, mix["decode_rows"], mix["prefill_tokens"])
    flops += 2.0 * rows * s["D"] * s["V"]
    lines = mix["decode_ctx"] + mix["prefill_row_ctx"] + tokens
    nbytes = BYTES * (per_token + s["D"] * s["V"]
                      + s["n_attn"] * s["kv_line"] * lines)
    nbytes += s["n_ssm"] * state_bytes(s, rows)
    return flops, nbytes

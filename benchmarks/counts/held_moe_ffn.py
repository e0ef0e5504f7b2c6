"""Operations and bytes ONE sparse layer's routed expert FFN needs for
the tokens that exist where the chip holds a RANGE of the router's
experts (the grouped matmuls of ``transformer.routed_experts_ffn``
under ``experts_held``): the FLOPs of the routed (token, expert) pairs
that fall on the experts held, the weights of the held experts hit
once (``qwen3_next_sizes.experts_hit``: expected under even routing),
the pairs' rows read and written. bf16 (2 bytes). ``mix`` as in
``counts/step.py``."""
from .qwen3_next_sizes import experts_hit, pairs_held, sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    pairs = pairs_held(s, tokens)
    flops = 2.0 * pairs * s["expert"]
    nbytes = BYTES * (experts_hit(s, tokens) * s["expert"] + 2 * pairs * s["D"])
    return flops, nbytes

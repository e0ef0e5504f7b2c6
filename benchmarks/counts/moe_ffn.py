"""Operations and bytes ONE sparse layer's routed expert FFN needs for
the tokens that exist (the three grouped matmuls of
``transformer.routed_experts_ffn``): the routed (token, expert) pairs'
FLOPs, the weights of the experts hit once, the pairs' rows read and
written. bf16 (2 bytes). ``mix`` as in ``counts/step.py``."""
from .lfm2_sizes import experts_hit, pairs_held, sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    pairs = pairs_held(s, tokens)
    flops = 2.0 * pairs * s["expert"]
    nbytes = BYTES * (experts_hit(s, tokens) * s["expert"] + 2 * pairs * s["D"])
    return flops, nbytes

"""Operations and bytes ONE sparse layer's routed expert FFN needs for
the tokens that exist where EVERY expert of the layer is on the chip
(the three grouped matmuls of ``transformer.routed_experts_ffn`` at
``num_experts`` groups, ``counts/laguna_sizes.py``): the routed (token,
expert) pairs' FLOPs, the weights of the experts hit once (expected
under even routing: at some 4000 pairs over 256 experts, all of them),
the pairs' rows read and written. The shared expert is not part of the
call and not counted. bf16 (2 bytes). ``mix`` as in ``counts/step.py``."""
from .laguna_sizes import experts_hit, pairs_held, sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    pairs = pairs_held(s, tokens)
    flops = 2.0 * pairs * s["expert"]
    nbytes = BYTES * (experts_hit(s, tokens) * s["expert"] + 2 * pairs * s["D"])
    return flops, nbytes

"""Operations and bytes ONE sparse layer's routed expert FFN needs for
the tokens that exist, for a decoder configuration FILE of the generic
family (``num_local_experts`` experts of ``intermediate_size``,
``num_experts_per_tok`` a token: Mixtral's keys, ``counts/sizes.py``):
the three grouped matmuls of ``transformer.routed_experts_ffn`` over the
routed (token, expert) pairs' FLOPs, the weights of the experts hit
once (expected under even routing), the pairs' rows read and written.
bf16 (2 bytes). ``mix`` as in ``counts/step.py``. ``counts/moe_ffn.py``
is the same count over LFM2's keys."""
from .sizes import sizes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    pairs = tokens * s["K"]
    hit = s["E"] * (1.0 - (1.0 - s["K"] / s["E"]) ** max(tokens, 0.0))
    flops = 2.0 * pairs * s["expert"]
    nbytes = BYTES * (hit * s["expert"] + 2 * pairs * s["D"])
    return flops, nbytes

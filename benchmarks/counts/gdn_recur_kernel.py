"""Operations and bytes ONE call of the gated delta rule's kernel
(``ff_gdn_recur_c1``: one recurrent layer's state update of one C=1
decode step) needs for the rows that exist (``mix`` as in
``counts/step.py``; a decode step has no prefilling row): each row's
float32 state read once and written once BY ITS ARITHMETIC (H dk dv
values: a layout that pads them moves more and reads lower), the row's
vectors in (k and q a head, and v, the decay, the write strength and
``k . q`` as the kernel takes them, a value a lane of the head) and
``o`` out, all float32, and the recurrence's ``7 dk dv`` operations a
head (``olmo_hybrid_sizes.delta_rule_flops``: the decay, ``S^T k``,
the rank-one update and ``S^T q``). The kernel cannot move a row's
state less than once each way, so its share of this cannot pass 100."""
from .olmo_hybrid_sizes import sizes

F32 = 4


def count(cfg, mix):
    s = sizes(cfg)
    rows = mix["decode_rows"]
    flops = 7.0 * rows * s["Hl"] * s["dk"] * s["dv"]
    vectors = s["Hl"] * (2 * s["dk"] + 5 * s["dv"])     # k, q; v, a, b, k . q in, o out
    return flops, F32 * rows * (2.0 * s["state"] + vectors)

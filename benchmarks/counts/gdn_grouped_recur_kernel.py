"""Operations and bytes ONE call of the gated delta rule's kernel
(``ff_gdn_recur_c1``) needs for the rows that exist where a key head
serves a group of value heads (``mix`` as in ``counts/step.py``; a
decode step has no prefilling row): each row's float32 state read once
and written once BY ITS ARITHMETIC (Hv dk dv values), the row's vectors
in (k and q ONCE A KEY HEAD; v, the decay, the write strength and
``k . q`` as the kernel takes them, a value a lane of the VALUE head)
and ``o`` out, all float32, and the recurrence's ``7 dk dv`` operations
a value head. The kernel cannot move a row's state less than once each
way, so its share of this cannot pass 100."""
from .qwen3_next_sizes import sizes

F32 = 4


def count(cfg, mix):
    s = sizes(cfg)
    rows = mix["decode_rows"]
    flops = 7.0 * rows * s["Hv"] * s["dk"] * s["dv"]
    # k, q a key head; v, a, b, k . q in and o out a value head
    vectors = 2 * s["Hk"] * s["dk"] + 5 * s["Hv"] * s["dv"]
    return flops, F32 * rows * (2.0 * s["state"] + vectors)

"""Operations and bytes the RECURRENT layers' mixers of one step program
need for the tokens that exist where a key head serves a group of value
heads (``mix`` as in ``counts/step.py``), every recurrent layer of the
step together, since the time they are held against is the step's
device time under the scope ``ff.mixer``: each layer's mixer weights
once (``W_qkvz``, ``W_ba``, ``W_o``), its float32 state and bf16
convolution state read and written once a row that steps, the tokens'
rows in and out, and the FLOPs of real tokens: the projections, the
taps, the gated delta rule (``qwen3_next_sizes.delta_rule_flops``: the
state's arithmetic a VALUE head, ``k k^T`` and ``q k^T`` a KEY head).
It bounds an XLA mixer and a Pallas one alike."""
from .qwen3_next_sizes import delta_rule_flops, sizes, state_bytes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    flops = 2.0 * tokens * s["gdn_mixer"] + delta_rule_flops(
        s, mix["decode_rows"], mix["prefill_tokens"])
    nbytes = (BYTES * (s["gdn_mixer"] + 2 * tokens * s["D"])
              + state_bytes(s, rows))
    return s["n_gdn"] * flops, s["n_gdn"] * nbytes

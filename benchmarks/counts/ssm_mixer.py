"""Operations and bytes the MAMBA layers' mixers of one step program
need for the tokens that exist (``mix`` as in ``counts/step.py``),
every mamba layer of the step together, since the time they are held
against is the step's device time under the scope ``ff.mixer``: each
layer's mixer weights once (``W_in``, ``W_o``), its float32 state and
bf16 convolution state read and written once a row that steps, the
tokens' rows in and out, and the FLOPs of real tokens: the projections,
the taps, the scan (the recurrence for a row of one token, the chunk
form for a prefilling row's tokens:
``granite_hybrid_sizes.scan_flops``). It bounds an XLA mixer and a
Pallas one alike."""
from .granite_hybrid_sizes import scan_flops, sizes, state_bytes

BYTES = 2


def count(cfg, mix):
    s = sizes(cfg)
    tokens = mix["decode_rows"] + mix["prefill_tokens"]
    rows = mix["decode_rows"] + mix["prefill_rows"]
    flops = 2.0 * tokens * s["ssm_mixer"] + scan_flops(
        s, mix["decode_rows"], mix["prefill_tokens"])
    nbytes = (BYTES * (s["ssm_mixer"] + 2 * tokens * s["D"])
              + state_bytes(s, rows))
    return s["n_ssm"] * flops, s["n_ssm"] * nbytes

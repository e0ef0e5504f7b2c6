"""Device milliseconds a step under the scope ``ff.attn.core``: the
attention call itself: the Pallas paged kernel or the XLA gather-and-
attend, with the spread of packed queries and the gather of the result.
The summed durations of the traced window's ``XLA Ops`` events
(container opcodes left out) inside ``jit_ff_step_*`` modules whose
instruction the program's scope map puts under ``ff.attn.core``, over
the number of those modules (``harness/sublayers.py``). None where the
cell has no such operation, without a trace, and on a program that gives
no map."""
from benchmarks.harness import sublayers


def read(ctx):
    return sublayers.read(ctx, "attn_core")

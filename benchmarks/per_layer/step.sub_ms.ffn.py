"""Device milliseconds a step under the scope ``ff.ffn``: the dense FFN, a
sparse layer's expert matmuls (grouped kernels or the all-expert einsum)
and its shared expert. The summed durations of the traced window's ``XLA
Ops`` events (container opcodes left out) inside ``jit_ff_step_*``
modules whose instruction the program's scope map puts under ``ff.ffn``,
over the number of those modules (``harness/sublayers.py``). None where
the cell has no such operation, without a trace, and on a program that
gives no map."""
from benchmarks.harness import sublayers


def read(ctx):
    return sublayers.read(ctx, "ffn")

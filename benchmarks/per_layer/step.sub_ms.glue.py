"""Device milliseconds a step under the scope ``ff.glue``: what a step does
between its sublayers: the embedding, the blocks' norms and residual
adds, the token packing, the masks and page lookups, the fetch packing.
The summed durations of the traced window's ``XLA Ops`` events
(container opcodes left out) inside ``jit_ff_step_*`` modules whose
instruction the program's scope map puts under ``ff.glue``, over the
number of those modules (``harness/sublayers.py``). None where the cell
has no such operation, without a trace, and on a program that gives no
map."""
from benchmarks.harness import sublayers


def read(ctx):
    return sublayers.read(ctx, "glue")

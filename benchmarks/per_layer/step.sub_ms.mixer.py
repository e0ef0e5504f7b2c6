"""Device milliseconds a step under the scope ``ff.mixer``: a token mixer
that is not attention (lightning linear attention, the gated short
convolution), with its state's read and write. The summed durations of
the traced window's ``XLA Ops`` events (container opcodes left out)
inside ``jit_ff_step_*`` modules whose instruction the program's scope
map puts under ``ff.mixer``, over the number of those modules
(``harness/sublayers.py``). None where the cell has no such operation,
without a trace, and on a program that gives no map."""
from benchmarks.harness import sublayers


def read(ctx):
    return sublayers.read(ctx, "mixer")

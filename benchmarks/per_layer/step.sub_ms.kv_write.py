"""Device milliseconds a step under the scope ``ff.attn.write``: the step's
new lines into the page pool, with whatever quantizing and layout copies
hang on the write. The summed durations of the traced window's ``XLA
Ops`` events (container opcodes left out) inside ``jit_ff_step_*``
modules whose instruction the program's scope map puts under
``ff.attn.write``, over the number of those modules
(``harness/sublayers.py``). None where the cell has no such operation,
without a trace, and on a program that gives no map."""
from benchmarks.harness import sublayers


def read(ctx):
    return sublayers.read(ctx, "kv_write")

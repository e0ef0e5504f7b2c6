"""Device milliseconds a step under the scope ``ff.moe.route``: the
router's matmul and top-k, the grouping of the routed pairs (sort,
scatters, tile alignment), the weighted combine back to token order and
the per-expert counts. The summed durations of the traced window's ``XLA
Ops`` events (container opcodes left out) inside ``jit_ff_step_*``
modules whose instruction the program's scope map puts under
``ff.moe.route``, over the number of those modules
(``harness/sublayers.py``). None where the cell has no such operation,
without a trace, and on a program that gives no map."""
from benchmarks.harness import sublayers


def read(ctx):
    return sublayers.read(ctx, "moe_route")

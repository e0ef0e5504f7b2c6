"""Device milliseconds a step under NO ``ff.*`` scope: the compiler's own
copies, a family that forgot a scope, and any instruction name the
program's scope map lacks (the executable that ran was then another; the
``[sublayers]`` lines count those). The same sum as the other nine
``step.sub_ms.*`` (``harness/sublayers.py``), which with this one add up
to the mean step's operation time. 0 where a step named every operation;
None without a trace or a map."""
from benchmarks.harness import sublayers


def read(ctx):
    return sublayers.read(ctx, "unscoped")

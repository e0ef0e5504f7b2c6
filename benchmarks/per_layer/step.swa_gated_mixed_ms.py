"""Mean device time of a C=chunk mixed step of a configuration whose
window and full layers differ in their head count (Laguna), in the
traced sub-window: ``step.swa_mixed_ms``'s reading (by count over every
program in which an ``ff_ragged_paged_c<chunk>_win`` call starts: the
packed rungs and the padded step), under a name of its own because its
roofline's count is this family's. None where no program holds one."""
from benchmarks.harness import spec


def step_ms(ctx):
    return spec.load_module("per_layer", "step.swa_mixed_ms").step_ms(ctx)


def read(ctx):
    return step_ms(ctx)

"""Mean device time of a C=chunk mixed step of a configuration whose
layer is two latent attentions, two dense FFNs and a routed block on a
shortcut (LongCat-Flash), in the traced sub-window:
``step.mla_mixed_ms``'s reading (by count over every program that holds
an ``ff_mla_paged_c<chunk>`` call: the packed rungs and the padded
step), under a name of its own because its roofline's count is this
family's. None where no program holds one."""
from benchmarks.harness import spec


def step_ms(ctx):
    return spec.load_module("per_layer", "step.mla_mixed_ms").step_ms(ctx)


def read(ctx):
    return step_ms(ctx)

"""Least time of the mamba layers' mixers in the C=chunk mixed step at
the traced window's mean mix (``counts/ssm_mixer.py``: their weights
once, the states of the rows that step, the chunk form's FLOPs for a
prefilling row's tokens and the recurrence's for a decoding row's) over
the device time a mixed step spends under the scope ``ff.mixer``, the
MEAN by count over every mixed program that ran (the packed rungs and
the padded step)."""
from benchmarks.harness import spec


def read(ctx):
    decode = spec.load_module("per_layer", "mixer.ssm_roofline.decode")
    return decode.read(ctx, "mixed", ctx.engine_serving.mixed_chunk)

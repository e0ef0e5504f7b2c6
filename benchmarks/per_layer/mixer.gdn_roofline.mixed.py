"""Least time of the recurrent layers' mixers in the C=chunk mixed step
at the traced window's mean mix (``counts/gdn_mixer.py``: their weights
once, the states of the rows that step, the chunk form's FLOPs for a
prefilling row's tokens and the recurrence's for a decoding row's) over
the device time a mixed step spends under the scope ``ff.mixer``, the
MEAN by count over every mixed program that ran (the packed rungs and
the padded step: ``mixer.gdn_roofline.decode`` has the reduction)."""
from benchmarks.harness import roofline, spec


def read(ctx):
    decode = spec.load_module("per_layer", "mixer.gdn_roofline.decode")
    ms = decode.scope_ms(ctx, ctx.engine_serving.mixed_chunk)
    return roofline.share(ctx, "gdn_mixer", "mixed", ms and ms / 1e3,
                          "mixer.gdn.mixed")

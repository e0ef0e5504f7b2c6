"""Least time of the recurrent layers' mixers in the C=1 decode step
where a key head serves a group of value heads
(``counts/gdn_grouped_mixer.py``: their weights once, the recurrent and
convolution states of the rows that step read and written once, the
FLOPs of real tokens) over the device time a decode step spends under
the scope ``ff.mixer`` (``per_layer/mixer.gdn_roofline.decode.py``'s
``scope_ms``: it reads the SCOPE, so the same count bounds an XLA mixer
and a Pallas one). None where the cell has no such operation, without
a trace, and on a program that gives no map."""
from benchmarks.harness import roofline, spec


def read(ctx):
    scope_ms = spec.load_module("per_layer", "mixer.gdn_roofline.decode").scope_ms
    ms = scope_ms(ctx, 1)
    return roofline.share(ctx, "gdn_grouped_mixer", "decode", ms and ms / 1e3,
                          "mixer.gdn_grouped.decode")

"""Least time of the mamba layers' mixers in the C=1 decode step
(``counts/ssm_mixer.py``: their weights once, the state-space and
convolution states of the rows that step read and written once, the
FLOPs of real tokens) over the device time a decode step spends under
the scope ``ff.mixer`` (``mixer.gdn_roofline.decode`` has the
reduction: the traced window's ``XLA Ops`` events inside
``jit_ff_step_c1*`` modules that the program's scope map puts there,
over the number of those modules). It reads the SCOPE, so the same count
bounds an XLA mixer and a Pallas one. None where the cell has no such
operation, without a trace, and on a program that gives no map."""
from benchmarks.harness import roofline, spec


def read(ctx, kind="decode", chunk=1):
    scope_ms = spec.load_module("per_layer", "mixer.gdn_roofline.decode").scope_ms
    ms = scope_ms(ctx, chunk)
    return roofline.share(ctx, "ssm_mixer", kind, ms and ms / 1e3,
                          f"mixer.ssm.{kind}")

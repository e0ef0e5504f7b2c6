"""Least time of the recurrent layers' mixers in the C=1 decode step
(``counts/gdn_mixer.py``: their weights once, the recurrent and
convolution states of the rows that step read and written once, the
FLOPs of real tokens) over the device time a decode step spends under
the scope ``ff.mixer``: the summed durations of the traced window's
``XLA Ops`` events inside ``jit_ff_step_c1*`` modules whose instruction
the program's scope map puts there, over the number of those modules
(``harness/sublayers.py``, ``Table.by_program``). It reads the SCOPE,
so the same count bounds an XLA mixer and a Pallas one. None where the
cell has no such operation, without a trace, and on a program that
gives no map."""
import re

from benchmarks.harness import roofline, sublayers

SCOPE = "ff.mixer"


def scope_ms(ctx, chunk):
    """Mean device ms a step under :data:`SCOPE` over the executed
    step programs of ``chunk`` (every rung and sampling head of it),
    by count; None where none ran or none holds the scope."""
    tab = sublayers.table(ctx)
    if tab is None:
        return None
    name = re.compile(rf"^jit_ff_step_c{chunk}(_|$)")
    rows = [row for program, row in tab.by_program.items()
            if name.match(program) and SCOPE in row["ms"]]
    steps = sum(row["steps"] for row in rows)
    return sum(row["ms"][SCOPE] for row in rows) / steps if steps else None


def read(ctx):
    ms = scope_ms(ctx, 1)
    return roofline.share(ctx, "gdn_mixer", "decode", ms and ms / 1e3,
                          "mixer.gdn.decode")

"""Least time of ONE sparse layer's routed expert FFN in the C=chunk
mixed step at the traced window's mean mix (``counts/routed_ffn.py``)
over the device time that layer's grouped matmuls take: in each
executed mixed program (``Trace.programs[chunk]``: every packed rung
and the padded step) the summed durations of the ``XLA Ops`` events
whose HLO instruction is named ``ff_moe_grouped*`` (the Pallas grouped
matmuls of ``transformer.routed_experts_ffn``), over the sparse layers;
the MEAN over programs by count, since the programs are of two or three
widths and a median would sit in one group or the other
(``step.mixed_mean_ms``). None where no operation carries such a name:
a program that computes every expert for every token (before PR 36)."""
import bisect

from benchmarks.harness import roofline

NAMES = ("ff_moe_grouped",)


def layer_ms(ctx):
    t = ctx.trace
    ops = sorted((s, dur) for n, _, _, _, s, dur in getattr(t, "ops", ())
                 if n.startswith(NAMES))
    if not ops:
        return None
    layers = ctx.cfg["num_hidden_layers"]
    starts = [o[0] for o in ops]
    out = []
    for s, e, *_ in t.programs.get(ctx.engine_serving.mixed_chunk, []):
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        if j > i:
            out.append(sum(dur for _, dur in ops[i:j]) / 1e6 / layers)
    return sum(out) / len(out) if out else None


def read(ctx):
    ms = layer_ms(ctx)
    return roofline.share(ctx, "routed_ffn", "mixed", ms and ms / 1e3,
                          "moe.ffn.mixed")

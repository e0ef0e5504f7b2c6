"""Least time of ONE sparse layer's routed expert FFN in the C=chunk
mixed step where every expert of the layer is on the chip
(``counts/all_held_ffn.py``) over the device time that layer's grouped
matmuls take: in each executed mixed program (``Trace.programs[chunk]``:
every packed rung and the padded step) the summed durations of the
``XLA Ops`` events whose HLO instruction is named ``ff_moe_grouped*``,
over the configuration's SPARSE layers (its dense layers have no such
call); the mean over programs by count (``moe.ffn_roofline.mixed``).
None where no operation carries such a name."""
import bisect

from benchmarks.harness import roofline, spec

NAMES = ("ff_moe_grouped",)


def layer_ms(ctx):
    t = ctx.trace
    ops = sorted((s, dur) for n, _, _, _, s, dur in getattr(t, "ops", ())
                 if n.startswith(NAMES))
    if not ops:
        return None
    layers = spec.load_module("counts", "laguna_sizes").sizes(ctx.cfg)["n_sparse"]
    starts = [o[0] for o in ops]
    out = []
    for s, e, *_ in t.programs.get(ctx.engine_serving.mixed_chunk, []):
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        if j > i:
            out.append(sum(dur for _, dur in ops[i:j]) / 1e6 / layers)
    return sum(out) / len(out) if out else None


def read(ctx):
    ms = layer_ms(ctx)
    return roofline.share(ctx, "all_held_ffn", "mixed", ms and ms / 1e3,
                          "moe.all_held_ffn.mixed")

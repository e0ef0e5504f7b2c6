"""Least time of ONE sparse layer's routed expert FFN in the C=1 decode
step where the chip holds a range of the experts
(``counts/held_moe_ffn.py``) over the device time that layer's grouped
matmuls take: in each executed decode program (``Trace.programs[1]``)
the summed durations of the ``XLA Ops`` events whose HLO instruction is
named ``ff_moe_grouped*`` (the Pallas grouped matmuls) or
``ragged-dot*`` (what ``lax.ragged_dot`` becomes on the chip), over the
configuration's sparse layers; the median over programs. None where no
operation carries such a name (a program without the routed layer)."""
import bisect

from benchmarks.harness import roofline, spec, stats

NAMES = ("ragged-dot", "ff_moe_grouped")


def layer_ms(ctx):
    t = ctx.trace
    ops = sorted((s, dur) for n, _, _, _, s, dur in getattr(t, "ops", ())
                 if n.startswith(NAMES))
    if not ops:
        return None
    layers = spec.load_module("counts", "qwen3_next_sizes").sizes(ctx.cfg)["n_layers"]
    starts = [o[0] for o in ops]
    out = []
    for s, e, *_ in t.programs.get(1, []):
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        if j > i:
            out.append(sum(dur for _, dur in ops[i:j]) / 1e6 / layers)
    return stats.median(out)


def read(ctx):
    ms = layer_ms(ctx)
    return roofline.share(ctx, "held_moe_ffn", "decode", ms and ms / 1e3,
                          "moe.held_ffn.decode")

"""Least time of ONE sparse layer's routed expert FFN in the C=1 decode
step (``counts/moe_ffn.py``) over the device time that layer's grouped
matmuls take: in each executed decode program (``Trace.programs[1]``)
the summed durations of the ``XLA Ops`` events whose HLO instruction is
named ``ragged-dot*`` (what ``lax.ragged_dot`` becomes on the chip: the
three grouped matmuls ``ragged-dot-none*`` and their shared
``ragged-dot-metadata*``; names read from one trace, PR 34) or
``ff_moe_grouped*`` (a Pallas grouped matmul, should one replace them),
over the sparse layers; the median over programs. None where no
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
    layers = spec.load_module("counts", "lfm2_sizes").sizes(ctx.cfg)["n_sparse"]
    starts = [o[0] for o in ops]
    out = []
    for s, e, *_ in t.programs.get(1, []):
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        if j > i:
            out.append(sum(dur for _, dur in ops[i:j]) / 1e6 / layers)
    return stats.median(out)


def read(ctx):
    ms = layer_ms(ctx)
    return roofline.share(ctx, "moe_ffn", "decode", ms and ms / 1e3,
                          "moe.ffn.decode")

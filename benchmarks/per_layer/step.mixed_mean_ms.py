"""Mean device time of a C=chunk mixed step in the traced sub-window,
by count over EVERY program that holds the chunk's kernel call. Since
PR 32 the mixed step is one of a few programs (the packed rungs
``ff_step_c<chunk>_t<width>`` and the padded ``ff_step_c<chunk>``,
``serve/engine.pack_widths``) whose times differ two- and fourfold, so
the median ``step.mixed_ms`` lands in one group or the other as the
counts shift; the mean by count is what a request waits for a step.
Logs each program's count and mean by its ``XLA Modules`` name. On a
program with one mixed step (before PR 32, a family that is not
packed) it reads that program's mean. None without a trace."""


def read(ctx):
    t = ctx.trace
    runs = getattr(t, "programs", {}).get(ctx.engine_serving.mixed_chunk)
    if not runs:
        return None
    names = {s: n.split("(")[0] for n, s, _, _ in t.modules}
    by = {}
    for s, e, *_ in runs:
        by.setdefault(names[s], []).append((e - s) / 1e6)
    ctx.log("[pack] mixed step programs of the traced window, count and "
            "mean ms: " + ", ".join(
                f"{n} {len(ms)} x {sum(ms) / len(ms):.2f}"
                for n, ms in sorted(by.items())))
    return sum(map(sum, by.values())) / len(runs)

"""Mean device time of a C=chunk mixed step of a configuration with
window layers of their own class of page in the traced sub-window, by
count over EVERY program that holds a window layer's kernel call (the
packed rungs and the padded step, as ``step.mixed_mean_ms``): the
programs of the chunk in which an ``ff_ragged_paged_c<chunk>_win`` call
starts. None where no program holds one."""
import bisect


def step_ms(ctx):
    t = ctx.trace
    chunk = ctx.engine_serving.mixed_chunk
    name = f"ff_ragged_paged_c{chunk}_win"
    starts = sorted(s for n, _, _, kernel, s, _ in getattr(t, "ops", ())
                    if kernel and n.split(".")[0] == name)
    if not starts:
        return None
    runs = [(s, e) for s, e, *_ in t.programs.get(chunk, ())
            if bisect.bisect_left(starts, s) < bisect.bisect_left(starts, e)]
    return sum((e - s) / 1e6 for s, e in runs) / len(runs) if runs else None


def read(ctx):
    return step_ms(ctx)

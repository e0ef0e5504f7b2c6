"""Mean device time of a C=chunk mixed step of a latent-attention
configuration in the traced sub-window, by count over EVERY program
that holds the latent kernel's call (the packed rungs and the padded
step, as ``step.mixed_mean_ms``): the programs whose first Pallas
kernel is ``ff_mla_paged_c<chunk>``. None where no program holds it."""
import bisect


def step_ms(ctx):
    t = ctx.trace
    chunk = ctx.engine_serving.mixed_chunk
    name = f"ff_mla_paged_c{chunk}"
    starts = sorted(s for n, _, _, kernel, s, _ in getattr(t, "ops", ())
                    if kernel and n.split(".")[0] == name)
    if not starts:
        return None
    runs = [(s, e) for s, e, *_ in t.programs.get(chunk, ())
            if bisect.bisect_left(starts, s) < bisect.bisect_left(starts, e)]
    return sum((e - s) / 1e6 for s, e in runs) / len(runs) if runs else None


def read(ctx):
    return step_ms(ctx)

"""Share of the mixed steps' dispatched token axis that held a real
token, over the window's pipelined mixed steps:
``SchedulerStats.step_tokens_real / step_tokens_width``. The engine
runs a mixed step's matmuls at the narrowest of a few compiled widths
that holds its real tokens (``serve/engine.pack_widths``); a step that
is not packed (a family with its own step, the dense layout) counts
slots x chunk as its width, so there this reads ``sched.mixed_fill_pct``
with the decoding rows added (``run.py`` logs the window's steps by
width beside it, in every run). None
where the server keeps no such counters (a program before PR 32) or
the window held no mixed step."""


def read(ctx):
    if not hasattr(ctx.window.stats_close, "step_tokens_width"):
        return None
    width = ctx.stats_delta("step_tokens_width")
    if not width:
        return None
    return 100.0 * ctx.stats_delta("step_tokens_real") / width

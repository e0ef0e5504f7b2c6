"""Share of the paged attention kernel's grid steps (rows x logical
pages of a layer's call, over the window's pipelined steps) that hold a
real query and a key it may see: ``SchedulerStats.attn_steps_live /
attn_steps_grid``, counted on the host under the causal mask. The kernel
computes those steps and skips the rest, so the share says how much of
a call's grid is work; ``attn_steps_narrow`` beside it counts the live
steps on rows of so few real queries that they take the kernel's narrow
body. None where the server keeps no such counters (a program before
PR 30, a layout with no pages)."""


def read(ctx):
    if not hasattr(ctx.window.stats_close, "attn_steps_grid"):
        return None
    grid = ctx.stats_delta("attn_steps_grid")
    return 100.0 * ctx.stats_delta("attn_steps_live") / grid if grid else None

"""Row tiles of the grouped expert matmuls that held a row, over the
experts that were given a token, over the window's flushed steps and
sparse layers: ``SchedulerStats.moe_tiles / moe_experts_hit`` (the
host counts, from the tokens per expert each step returns, the tiles
they fill under that step's own row tile, ``serve/kernels.grouped_tile``).
It says how many grid steps share one fetch of an expert's weight
block: several at an eighth of the MXU's rows compute for longer than
the block takes to arrive; 1 to 2 hide under it. None where the server
keeps no such counter (a program before PR 51, a family with no routed
layer)."""


def read(ctx):
    if not hasattr(ctx.window.stats_close, "moe_tiles"):
        return None
    hit = ctx.stats_delta("moe_experts_hit")
    return ctx.stats_delta("moe_tiles") / hit if hit else None

"""The mean context a decode row attends, in lines, over the window's
dispatched steps: ``SchedulerStats.decode_context_lines`` (a decode row
at position p attends p + 1 lines; summed over every decode row
dispatched) over ``decode_tokens`` (the decode rows dispatched). A full
layer's K/V read of a decode row follows it. None where the server
keeps no such counter (a program before PR 58) or no row decoded."""


def read(ctx):
    if not hasattr(ctx.window.stats_close, "decode_context_lines"):
        return None
    rows = ctx.stats_delta("decode_tokens")
    return ctx.stats_delta("decode_context_lines") / rows if rows else None

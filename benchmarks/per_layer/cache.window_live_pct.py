"""Pages of the window classes that were live at their peak, as a share
of what those classes' slots WOULD have held at such a step boundary
with nothing freed: ``SchedulerStats.window_pages_live_peak`` over
``window_pages_unfreed_peak`` (both read where a
step's pages are reserved, over the server's life up to the window's
close). About 35 at contexts of three windows, 100 if nothing is
freed. None where the server keeps no such counters (a program before
PR 50, a family with one class of page)."""


def read(ctx):
    stats = ctx.window.stats_close
    unfreed = getattr(stats, "window_pages_unfreed_peak", 0)
    return 100.0 * stats.window_pages_live_peak / unfreed if unfreed else None

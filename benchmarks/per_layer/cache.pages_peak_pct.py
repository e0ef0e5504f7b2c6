"""Most pages of the pool in use at a step boundary of the run, as a
share of the pool (``PageAllocator.used_pages / num_pages``)."""


def read(ctx):
    return 100.0 * ctx.window.pages_peak / ctx.pool_pages

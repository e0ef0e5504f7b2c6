"""Share of the real rows of the window's steps (prefilling or
decoding) whose last position lay above ``dense_len``, so that the
step took the block choice for them: ``SchedulerStats.sparse_rows /
real_rows``. It says whether the traffic reaches the mechanism. None
where the server keeps no such counters (a program before PR 29, a
family with no sparse layers)."""


def read(ctx):
    if not hasattr(ctx.window.stats_close, "sparse_rows"):
        return None
    rows = ctx.stats_delta("real_rows")
    return 100.0 * ctx.stats_delta("sparse_rows") / rows if rows else None

"""Share of the step programs' builds that JAX's persistent compilation
cache answered, of those that asked it, before the window opened:
``SchedulerStats.build_cache_hits`` over hits and misses at
``loop.run``'s opening snapshot (``flexflow_tpu/obs/builds.py``). 100 a
warm run, 0 a cold one; a program the cache will not keep (one that
compiles under its time threshold) misses every run. 0.0 and not
nothing where no build asked the cache. None where the server keeps no
such log (a program before PR 56)."""


def read(ctx):
    stats = ctx.window.stats_open
    if not hasattr(stats, "build_cache_hits"):
        return None
    asked = stats.build_cache_hits + stats.build_cache_misses
    return 100.0 * stats.build_cache_hits / asked if asked else 0.0

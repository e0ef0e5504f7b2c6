"""Median over the window's samples of the time a request waited for a
slot: ``ProfileInfo.admit_time`` (its FIRST slot grant, stamped by
``RequestManager._admit_pending``) less its due time. The first of the
three parts of TTFT; with ``sched.prefill_dispatch_ms`` and
``engine.first_token_lag_ms`` it sums to ``ttft_ms`` sample by sample.
None where the server stamps no admission (a program before PR 27)."""
from benchmarks.harness import stats


def read(ctx):
    return stats.median([
        (s.profile.admit_time - s.due) * 1e3 for s in ctx.window.samples
        if getattr(s.profile, "admit_time", 0.0)])

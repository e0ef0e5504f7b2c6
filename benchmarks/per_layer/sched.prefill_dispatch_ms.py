"""Median over the window's samples of the time from a request's first
slot grant (``ProfileInfo.admit_time``) to the dispatch of the step that
carried its prompt's final chunk (``prefill_dispatched_time``): the
chunked prefill as the host paced it, about one step's device time per
chunk once the dispatch-ahead queue is full. The second part of TTFT.
None where the server stamps neither (a program before PR 27)."""
from benchmarks.harness import stats


def read(ctx):
    return stats.median([
        (s.profile.prefill_dispatched_time - s.profile.admit_time) * 1e3
        for s in ctx.window.samples
        if getattr(s.profile, "prefill_dispatched_time", 0.0)
        and s.profile.admit_time])

"""Median over the window's samples of the time from the dispatch of
the step that carried a prompt's final chunk
(``ProfileInfo.prefill_dispatched_time``) to the host seeing its sample
(``first_token_time``, stamped at the flush): the steps queued ahead of
it on the device, its own device time and the flush's lag,
``dispatch_ahead`` steps if the host runs that far ahead. The third
part of TTFT. None where the server stamps no dispatch (a program
before PR 27)."""
from benchmarks.harness import stats


def read(ctx):
    return stats.median([
        (s.first_token - s.profile.prefill_dispatched_time) * 1e3
        for s in ctx.window.samples
        if getattr(s.profile, "prefill_dispatched_time", 0.0)
        and s.first_token])

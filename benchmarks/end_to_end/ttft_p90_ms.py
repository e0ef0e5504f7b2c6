"""90th percentile, over the window's samples that got a first
token, of the time from a request's DUE time to the host seeing its
first token (the server stamps that at the flush, which is when a
streaming client would see it)."""
from benchmarks.harness.stats import percentile


def read(ctx):
    return percentile([s.ttft_ms for s in ctx.window.samples], 90.0)

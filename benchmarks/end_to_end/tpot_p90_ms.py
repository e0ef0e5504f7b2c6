"""90th percentile over the window's completed samples of a request's
mean gap between output tokens: (last token - first token) /
(output tokens - 1)."""
from benchmarks.harness.stats import percentile


def read(ctx):
    return percentile([s.gap_ms for s in ctx.window.samples], 90.0)

"""Share of the traced sub-window in which no operation ran on the
device: 1 - the union of the ``XLA Ops`` intervals over the window.
With 20 (4) of 32 layers the host's share is larger than in a
deployment."""


def read(ctx):
    return ctx.trace.idle_pct

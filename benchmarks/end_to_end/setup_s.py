"""Process start to the window opening: imports, weights, compile or
cache load, the reference probe, traffic warm-up."""


def read(ctx):
    return ctx.setup_s

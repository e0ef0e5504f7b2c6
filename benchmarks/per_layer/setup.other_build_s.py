"""Seconds of every other build of the process before the window
opened, from the first engine's construction on: the probe's and the
float32 reference's programs, ``jnp`` helpers run outside any program —
the harness's share. ``SchedulerStats.build_other_s`` at ``loop.run``'s
opening snapshot: trace, lowering and backend compile of every program
``InferenceEngine._jit`` did not name, outermost parts only
(``flexflow_tpu/obs/builds.py``). None where the server keeps no such
log (a program before PR 56)."""


def read(ctx):
    return getattr(ctx.window.stats_open, "build_other_s", None)

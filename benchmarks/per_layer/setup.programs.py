"""Step programs built before the window opened: the records of
``SchedulerStats.builds`` at ``loop.run``'s opening snapshot (the
instant ``setup_s`` ends) less the ``other`` one. Builds, not keys: a
retrace is a record of its own (``ff_step_c1#2``). The engine's build
log (``flexflow_tpu/obs/builds.py``) opens a record where
``InferenceEngine._jit``'s wrapper runs, which is when a program is
traced. None where the server keeps no such log (a program before
PR 56)."""


def read(ctx):
    builds = getattr(ctx.window.stats_open, "builds", None)
    if builds is None:
        return None
    return sum(name != "other" for name in builds)

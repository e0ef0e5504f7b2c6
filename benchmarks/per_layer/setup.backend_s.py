"""Seconds the backend took to compile the step programs before the
window opened, or the compilation cache to load them where it hit:
``SchedulerStats.build_backend_s`` at ``loop.run``'s opening snapshot
(JAX's ``backend_compile_duration`` over the programs
``InferenceEngine._jit`` named, ``flexflow_tpu/obs/builds.py``). The
part of set-up that depends on the machine's cache:
``setup.cache_hit_pct`` says which kind a line holds. None where the
server keeps no such log (a program before PR 56)."""


def read(ctx):
    return getattr(ctx.window.stats_open, "build_backend_s", None)

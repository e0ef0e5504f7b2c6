"""Seconds of lowering the step programs' jaxprs to MLIR before the
window opened, Mosaic's lowering of each Pallas call with it:
``SchedulerStats.build_lower_s`` at ``loop.run``'s opening snapshot
(JAX's ``jaxpr_to_mlir_module_duration`` over the programs
``InferenceEngine._jit`` named, ``flexflow_tpu/obs/builds.py``). A
compilation-cache hit skips none of it. None where the server keeps no
such log (a program before PR 56)."""


def read(ctx):
    return getattr(ctx.window.stats_open, "build_lower_s", None)

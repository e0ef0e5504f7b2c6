"""How far the host runs ahead of the device: median over the step
programs of the traced window (``XLA Modules`` events named
``jit_ff_step_*``) of the program's device start less the start of the
host's ``DoEnqueueProgram`` event with the same ``run_id``. That event
runs on a runtime thread, but it is not itself queued there: read by
hand (PERF.md section 3) it starts about half a millisecond after the
jitted call begins on the Python thread, inside ``ff.step.dispatch``,
so it is taken as the moment the host enqueued the step. None where no
program carries the ``ff_step`` name (a program before PR 27)."""
from benchmarks.harness import reduce, stats


def read(ctx):
    t = ctx.trace
    enqueued = {stt.get("run_id"): s for n, s, _, stt in getattr(t, "host", ())
                if n == reduce.ENQUEUE_EVENT}
    return stats.median([
        (s - enqueued[stt.get("run_id")]) / 1e6
        for n, s, dur, stt in getattr(t, "modules", ())
        if n.startswith("jit_ff_step_") and t.lo <= s and s + dur <= t.hi
        and stt.get("run_id") in enqueued])

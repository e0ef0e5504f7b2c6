"""Seconds of Python tracing of the step programs before the window
opened: ``SchedulerStats.build_trace_s`` at ``loop.run``'s opening
snapshot, the sum of JAX's own ``jaxpr_trace_duration`` over the
programs ``InferenceEngine._jit`` named (``flexflow_tpu/obs/builds.py``).
A compilation-cache hit skips none of it. Logs one ``[builds]`` line a
program — ordinal, the three parts, what the cache said, the scheduler
step it began in, and the jitted functions traced inside its trace
(count x seconds, the three longest; ``wrapped`` is a bare
``pallas_call``'s body, traced once a call site; ``inner`` sums those
the program called itself, so trace less inner is its own Python) —
and the three sums beside ``setup_s``. None
where the server keeps no such log (a program before PR 56)."""


def read(ctx):
    stats = ctx.window.stats_open
    if not hasattr(stats, "build_trace_s"):
        return None
    t0 = ctx.window.opened - ctx.setup_s   # the process's start, on the records' clock
    for name, r in stats.builds.items():
        if name == "other":
            ctx.log(f"[builds] other: {r['count']} programs, trace "
                    f"{r['trace_s']:.2f}s lower {r['lower_s']:.2f}s backend "
                    f"{r['backend_s']:.2f}s, cache hits {r['cache_hits']} "
                    f"misses {r['cache_misses']}")
            continue
        inner = sorted(r["inner"].items(), key=lambda kv: -kv[1][1])[:3]
        ctx.log(
            f"[builds] {name} #{r['ordinal']} at +{r['start'] - t0:.1f}s "
            f"step {r['step']}: trace {r['trace_s']:.2f}s (inner "
            f"{r['inner_s']:.2f}s) lower {r['lower_s']:.2f}s backend "
            f"{r['backend_s']:.2f}s cache {r['cache']}"
            + (f" (load {r['cache_load_s']:.2f}s, saved {r['saved_s']:.2f}s)"
               if r["cache"] == "hit" else "")
            + "; inner " + (", ".join(
                f"{n} {int(c)} x {s:.2f}s" for n, (c, s) in inner) or "-"))
    ctx.log(f"[builds] step programs: trace {stats.build_trace_s:.2f}s + "
            f"lower {stats.build_lower_s:.2f}s + backend "
            f"{stats.build_backend_s:.2f}s, other builds "
            f"{stats.build_other_s:.2f}s, of setup_s {ctx.setup_s:.2f}s")
    return stats.build_trace_s

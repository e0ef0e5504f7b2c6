"""Device time a step by sublayer: what the ten ``step.sub_ms.*``
readers share.

The step programs carry ``ff.*`` named scopes (``flexflow_tpu/obs/
sublayers.py``); the profile's events do not (``trace.KEPT_STATS``
keeps ``run_id`` alone, and the profile is removed once read). The
PROGRAM gives the map: ``obs.sublayers.scope_maps()`` returns, for each
step program the process compiled, ``{HLO instruction name: sublayer or
None}`` from the compiled executable's own text, and an ``XLA Ops``
event's name starts with its instruction's name. The join is by the
``XLA Modules`` event the operation lies in (its program) and that
name.

:func:`reduce_sublayers` is the arithmetic, over plain data, so
``tests/test_sub_ms.py`` runs it on a recorded sample: the summed
durations of the operations (container opcodes left out, as
``Trace.breakdown`` does) that lie inside a module event named
``jit_ff_step_*``, by sublayer, over the NUMBER of those module
events: device milliseconds a step, the mean over every pipelined step
of the traced window, decode and mixed, whatever its rung. The ten sum
to the mean step's operation time.
"""
import bisect
import dataclasses
import time

from . import reduce

STEP_MODULE = "jit_ff_step_"
UNSCOPED = "unscoped"
_LACKS = object()  # a name a program's map does not hold
#: metric suffix (``step.sub_ms.<suffix>``) -> scope
METRICS = {
    "attn_proj": "ff.attn.proj",
    "attn_core": "ff.attn.core",
    "kv_write": "ff.attn.write",
    "attn_select": "ff.attn.select",
    "mixer": "ff.mixer",
    "ffn": "ff.ffn",
    "moe_route": "ff.moe.route",
    "head": "ff.head",
    "glue": "ff.glue",
    UNSCOPED: None,
}


@dataclasses.dataclass
class Table:
    steps: int        # module events named jit_ff_step_* in the window
    ms: dict          # scope (None: unscoped) -> summed ms of its operations
    by_program: dict  # program -> {"steps": n, "ms": {scope: ms}}
    unscoped: dict    # (program, "instruction opcode") under no scope -> ms
    unmatched: dict   # (program, instruction) the map lacks -> summed ms

    def per_step(self, scope):
        """Mean ms a step under ``scope``; None where no operation of
        the window lies under it. Under no scope (None) a step that
        named every operation reads 0: that metric is every cell's."""
        if not self.steps or (scope is not None and scope not in self.ms):
            return None
        return self.ms.get(scope, 0.0) / self.steps


def step_modules(trace):
    """``[(start, end, program name)]`` of the step programs that ran
    whole inside the traced window, in order."""
    return sorted(
        (s, s + dur, name.split("(")[0]) for name, s, dur, _ in trace.modules
        if name.startswith(STEP_MODULE) and trace.lo <= s
        and s + dur <= trace.hi)


def reduce_sublayers(trace, maps):
    """The :class:`Table` of ``trace`` (a ``reduce.Trace``) under
    ``maps`` (``scope_maps()``'s result). An operation whose
    instruction maps to None, whose name its program's map lacks, or
    whose program has no map, counts as unscoped; the last two also as
    unmatched."""
    steps = step_modules(trace)
    starts = [s for s, _, _ in steps]
    table = Table(len(steps), {}, {}, {}, {})
    for _, _, program in steps:
        row = table.by_program.setdefault(program, {"steps": 0, "ms": {}})
        row["steps"] += 1
    for name, _, opcode, _, s, dur in trace.ops:
        if opcode in reduce.CONTAINERS:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= steps[i][1]:
            continue  # outside every step program
        program = steps[i][2]
        scope = maps.get(program, {}).get(name, _LACKS)
        ms = dur / 1e6
        if scope is _LACKS:
            scope = None
            key = (program, name)
            table.unmatched[key] = table.unmatched.get(key, 0.0) + ms
        if scope is None:
            key = (program, f"{name} {opcode}")
            table.unscoped[key] = table.unscoped.get(key, 0.0) + ms
        table.ms[scope] = table.ms.get(scope, 0.0) + ms
        by = table.by_program[program]["ms"]
        by[scope] = by.get(scope, 0.0) + ms
    return table


def program_maps(programs):
    """The scope maps of ``programs`` from the program under test, or
    None where it has no such function (a tree before PR 42)."""
    try:
        from flexflow_tpu.obs.sublayers import scope_maps
    except ImportError:
        return None
    return scope_maps(programs=programs)


def _short(scope):
    return UNSCOPED if scope is None else scope[len("ff."):]


def table(ctx):
    """The traced window's :class:`Table`, made once for the ten
    readers and kept on the trace; None without a trace or a map. Logs
    a ``[sublayers]`` line a program (count, mean ms of its operations,
    ms by sublayer), the seconds the map took and the names it lacks."""
    t = ctx.trace
    if not hasattr(t, "ops"):  # NoTrace: a CPU rehearsal
        return None
    if not hasattr(t, "sublayers"):
        t.sublayers = None
        t0 = time.perf_counter()
        maps = program_maps({p for _, _, p in step_modules(t)})
        if maps:
            took = time.perf_counter() - t0
            t.sublayers = tab = reduce_sublayers(t, maps)
            ctx.log(f"[sublayers] scope maps of {len(maps)} programs in "
                    f"{took:.2f}s; {tab.steps} steps in the traced window; "
                    f"names the maps lack: {len(tab.unmatched)}")
            for program, row in sorted(tab.by_program.items()):
                by = sorted(row["ms"].items(), key=lambda kv: -kv[1])
                ctx.log(
                    f"[sublayers] {program} {row['steps']} x "
                    f"{sum(row['ms'].values()) / row['steps']:.3f} ms: "
                    + ", ".join(f"{_short(scope)} {ms / row['steps']:.3f}"
                                for scope, ms in by))
            for what, ops in (("unscoped", tab.unscoped),
                              ("unmatched", tab.unmatched)):
                worst = sorted(ops.items(), key=lambda kv: -kv[1])[:8]
                if worst:
                    ctx.log(f"[sublayers] {what}, the longest (ms a step): "
                            + ", ".join(f"{p}:{n} {ms / tab.steps:.4f}"
                                        for (p, n), ms in worst))
    return t.sublayers


def read(ctx, suffix):
    tab = table(ctx)
    return None if tab is None else tab.per_step(METRICS[suffix])

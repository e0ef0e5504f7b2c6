"""From a trace to numbers. Pure functions over the plain event lists
``trace.read_xplane`` returns, so the same arithmetic runs on the small
recorded trace kept in ``tests/`` — and the ``Context`` every metric
reader is handed.

Which planes and lines are read (looked at by hand first, PERF.md
section 3): the device plane ``/device:TPU:<n>`` — its line ``XLA Ops``
(one event per executed HLO operation: the busy time) and its line
``XLA Modules`` (one event per executed program: time per step
program) — and the host plane ``/host:CPU``, where the benchmark's own
``bench.*`` annotations and the flush's blocking fetch sit on the Python
thread's line (``python3``) and ``DoEnqueueProgram`` on the runtime's.
All on one clock. An op event's name is the operation's whole HLO text;
a ``while`` is an event and so is every operation of its body, so busy
time is a UNION of intervals.
"""
import bisect
import dataclasses
import re
from typing import Any

from . import stats as st

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATIONS = ("bench.submit", "bench.step")
TRACED = "bench.traced"
# host events inside a bench.step span during which the host only
# waits for the device (read by hand in one trace, PERF.md section 3)
WAIT_EVENTS = ("np.asarray(jax.Array)",)  # the flush's blocking fetch
ENQUEUE_EVENT = "DoEnqueueProgram"         # carries the program's run_id
CONTAINERS = ("while", "conditional", "call")  # their bodies' ops are events too
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'  # a Mosaic (Pallas) kernel


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def gaps(busy, lo, hi):
    """The complement of the merged ``busy`` inside [lo, hi)."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(intervals, cover):
    """Length of ``intervals`` covered by the merged list ``cover``."""
    starts = [c[0] for c in cover]
    got = 0.0
    for s, e in intervals:
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(cover) and cover[i][0] < e:
            got += max(0.0, min(e, cover[i][1]) - max(s, cover[i][0]))
            i += 1
    return got


def parse_op(text):
    """An ``XLA Ops`` event's name is the operation's HLO text,
    ``%name = shape opcode(operands), attributes``. Returns
    (name, shape without layouts, opcode, is a Pallas kernel)."""
    m = re.match(r"%?([\w.\-]+) = ", text)
    if not m:
        return text, "", "", False
    rest = text[m.end():]
    if rest.startswith("("):  # a tuple shape: to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return m.group(1), shape, rest.split("(")[0], KERNEL_TARGET in text


def compact_op(text):
    """The HLO text without operands — what a recorded sample keeps."""
    name, shape, opcode, kernel = parse_op(text)
    if len(shape) > 120:
        shape = "(...)" if shape.startswith("(") else shape[:120]
    return f"%{name} = {shape} {opcode}()" + (
        f", {KERNEL_TARGET}" if kernel else "")


def kernel_chunk(shape):
    """The ragged paged kernel is the only Pallas kernel of a step
    program (it has no ``pallas_call(name=...)`` yet, ROADMAP A2); its
    result is [slots, chunk, kv heads, group, head size], so the second
    extent says which step program it belongs to."""
    m = re.search(r"\[(\d+),(\d+),", shape)
    return int(m.group(2)) if m else None


class NoDevicePlane(RuntimeError):
    """The trace holds no TPU plane (a CPU rehearsal's does not)."""


class NoTrace:
    """Stands in for ``Trace`` until one is loaded (and in a CPU
    rehearsal for good): nothing to read, so every reader built on it
    returns nothing and the harness leaves its metric out."""

    idle_share = idle_pct = None

    def program_ms(self, chunk):
        return None

    kernel_call_ms = dispatch_ms = program_ms


class Trace:
    """The reduction of one traced sub-window."""

    def __init__(self, planes):
        devices = sorted(p for p in planes if DEVICE_PLANE.match(p))
        if not devices:
            raise NoDevicePlane(f"no device plane among {sorted(planes)}")
        host = planes.get(HOST_PLANE, {})
        self.host = [ev for line in host.values() for ev in line]
        traced = [ev for ev in self.host if ev[0] == TRACED]
        if traced:
            self.lo = traced[0][1]
            self.hi = traced[0][1] + traced[0][2]
        else:  # no span: the ops' own extent
            ops = [ev for d in devices for ev in planes[d].get(OPS_LINE, [])]
            self.lo = min(ev[1] for ev in ops)
            self.hi = max(ev[1] + ev[2] for ev in ops)
        self.window_s = (self.hi - self.lo) / 1e9
        self.n_devices = len(devices)
        busy_total = 0.0
        self.busy = None
        for d in devices:
            ops = planes[d].get(OPS_LINE, [])
            merged = union(clip([(s, s + dur) for _, s, dur, _ in ops],
                                self.lo, self.hi))
            busy_total += total(merged)
            if self.busy is None:  # the first device's ops, parsed once
                self.busy = merged
                self.ops = [parse_op(n) + (s, dur) for n, s, dur, _ in ops]
        self.busy_s = busy_total / self.n_devices / 1e9
        self.modules = planes[devices[0]].get(MODULES_LINE, [])
        self.programs = self._programs()

    # -- device ------------------------------------------------------
    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s

    @property
    def idle_pct(self):
        return 100.0 * self.idle_share

    def _programs(self):
        """``{chunk: [(start, end, kernel seconds, kernel calls, run
        id)]}``: each executed program of the window that holds a Pallas
        kernel, keyed by that kernel's chunk extent (1: the decode step,
        the mixed chunk: the mixed step)."""
        out = {}
        calls = sorted((s, dur, kernel_chunk(shape))
                       for _, shape, _, kernel, s, dur in self.ops if kernel)
        starts = [c[0] for c in calls]
        for _, s, dur, stats in self.modules:
            if s < self.lo or s + dur > self.hi:
                continue
            i = bisect.bisect_left(starts, s)
            inside = []
            while i < len(calls) and calls[i][0] < s + dur:
                inside.append(calls[i])
                i += 1
            if not inside or inside[0][2] is None:
                continue
            out.setdefault(inside[0][2], []).append(
                (s, s + dur, sum(c[1] for c in inside) / 1e9, len(inside),
                 stats.get("run_id")))
        return out

    def program_ms(self, chunk):
        """Median device time of the program of ``chunk``."""
        return st.median([(e - s) / 1e6
                          for s, e, *_ in self.programs.get(chunk, [])])

    def kernel_call_ms(self, chunk):
        """Median time of ONE kernel call in the program of ``chunk``."""
        return st.median([k * 1e3 / n
                          for _, _, k, n, _ in self.programs.get(chunk, [])])

    # -- host --------------------------------------------------------
    def spans(self, name):
        return clip([(s, s + d) for n, s, d, _ in self.host if n == name],
                    self.lo, self.hi)

    def idle_by_annotation(self):
        """Device idle seconds inside the window, by the benchmark's
        annotation that covers them (``bench.other``: none does)."""
        idle = gaps(self.busy, self.lo, self.hi)
        out, left = [], total(idle)
        for name in ANNOTATIONS:
            got = overlap(idle, union(self.spans(name)))
            left -= got
            out.append((name, got / 1e9))
        out.append(("bench.other", max(0.0, left) / 1e9))
        return out

    def dispatch_ms(self, chunk):
        """Host time of a ``bench.step`` that dispatched the program of
        ``chunk``, less the runtime's wait events inside it; median.
        The host runs up to ``dispatch_ahead`` steps ahead of the
        device, so a step is matched to its program not by time but by
        the ``run_id`` that the host's ``DoEnqueueProgram`` event and
        the device's module event both carry. None where the trace
        holds no wait event to subtract."""
        waits = union([(s, s + d) for n, s, d, _ in self.host
                       if n in WAIT_EVENTS])
        if not waits:
            return None
        wanted = {r[4] for r in self.programs.get(chunk, [])}
        enqueued = sorted((s, stt.get("run_id")) for n, s, _, stt in self.host
                          if n == ENQUEUE_EVENT)
        starts = [e[0] for e in enqueued]
        out = []
        for s, e in self.spans("bench.step"):
            i = bisect.bisect_left(starts, s)
            while i < len(enqueued) and enqueued[i][0] < e:
                if enqueued[i][1] in wanted:
                    out.append(((e - s) - overlap([(s, e)], waits)) / 1e6)
                    break
                i += 1
        return st.median(out)

    def breakdown(self):
        by_op = {}
        for name, shape, opcode, _, s, dur in self.ops:
            if self.lo <= s < self.hi and opcode not in CONTAINERS:
                key = f"{name} {opcode} {shape[:80]}"
                by_op[key] = by_op.get(key, 0.0) + dur / 1e9
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_annotation(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle if v > 0][:10]}


@dataclasses.dataclass
class Context:
    """What a metric reader is handed."""

    window: Any          # loop.Window
    setup_s: float
    cfg: dict            # the configuration file
    engine_serving: Any  # the server's ServingConfig (slots, chunk)
    peaks: Any           # this device's entry of peaks.json
    pool_pages: int = 0  # pages of the server's pool
    log: Any = print
    tracer: Any = None   # trace.Tracer of a --trace 1 run
    trace: Any = NoTrace()

    def load_trace(self):
        self.trace = Trace(self.tracer.events())
        t = self.trace
        self.log(f"[trace] window {t.window_s:.3f}s, device busy "
                 f"{t.busy_s:.3f}s on {t.n_devices} device(s); programs "
                 f"{ {c: len(r) for c, r in t.programs.items()} }")

    def stats_delta(self, field, sub=False):
        """A ``SchedulerStats`` counter's growth over the window (or,
        ``sub``, over the traced sub-window)."""
        a, b = ((self.tracer.stats_start, self.tracer.stats_stop) if sub
                else (self.window.stats_open, self.window.stats_close))
        return getattr(b, field) - getattr(a, field)

    def steps_by_width(self):
        """The window's pipelined mixed steps by the width the engine
        dispatched them at; None where the server keeps no such count
        (a program before PR 32)."""
        a = getattr(self.window.stats_open, "steps_by_width", None)
        if a is None:
            return None
        b = self.window.stats_close.steps_by_width
        return {w: n - a.get(w, 0) for w, n in sorted(b.items())}

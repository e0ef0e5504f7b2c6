"""Structured tracing — the span/event recorder under flexflow_tpu's
observability layer (ROADMAP: the telemetry substrate item 2's
self-driving serving loop reads).

Design constraints, in order:

1. **Disabled mode must be free.** Every EVENT site in the serve stack
   guards on ``tracer.enabled`` (a plain bool attribute read) before
   building ANY argument, and the module-level :data:`NULL_TRACER`
   never records — with tracing off no event dict is built and no
   buffer is touched (tests/test_observability.py proves it: zero
   obs-frame allocations, identical dispatched-program counts).
2. **One span primitive, on the profiler's clock.** ``tracer.span(name)``
   is called UNGUARDED: on either tracer it is a
   ``jax.profiler.TraceAnnotation("ff." + name)``, which the runtime
   records only while a profiler session is open — the session is the
   switch (``jax.profiler.start_trace``, xprof's capture, the
   benchmark's ``--trace 1``), so a capture of a serving process shows
   the spans with no set-up and one code path serves attached and
   unattached managers. With no session an annotation is a fraction of
   a microsecond (PERF.md has the chip host's reading). The scheduler
   step is cut into the six :data:`STEP_SPANS`; a layer's self time is
   its span less what its children cover.
3. **Dual clock in the buffer.** Every buffered event carries BOTH a
   wall-clock stamp and a deterministic step stamp (the owner's
   scheduler / cluster step counter, what tests assert on). The wall
   stamp ``t`` is ``time.perf_counter()`` — the clock ``ProfileInfo``'s
   stamps use, so a request's events and its stamps compare. It is NOT
   the profiler's clock and cannot be: an xplane's times are relative
   to its session's start, which Python cannot read. A span therefore
   lives in both places — as an annotation on the profiler's clock
   (beside the device's operations) and, with a live tracer, as a
   buffered event with a ``perf_counter`` duration. Nothing in the
   trace pipeline ever *decides* anything off wall time.
4. **Wire-safe events.** An event is one flat dict of codec-safe
   primitives (str/int/float/None — see serve/cluster/transport.py),
   so a remote replica's events ride the PR-12 RPC envelope unchanged
   and the client stitches one cross-host timeline
   (serve/cluster/{server,remote}.py).

One :class:`TraceBuffer` holds the run's events; components record
through per-lane :class:`Tracer` views (``buffer.tracer("replica0",
clock=...)``). Lanes become Perfetto process rows in the Chrome export
(obs/export.py); the optional :class:`~.flight_recorder.FlightRecorder`
observes every append for its bounded per-lane ring.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import jax

__all__ = ["TraceBuffer", "Tracer", "NullTracer", "NULL_TRACER",
           "STEP_SPANS", "BUILD_SPANS", "BUILD_ANNOTATION"]

#: The phases of one ``RequestManager.step`` (serve/request_manager.py).
#: In a profile they read ``ff.step.admit`` … on the Python thread's
#: line; ``step.flush_wait`` (the host waiting on the device for a
#: finished step's tokens) nests inside ``step.flush``, and a flush
#: forced by admission or page reservation nests inside that phase.
STEP_SPANS = (
    "step.admit",       # scheduler: slot grants, reclaiming flushes
    "step.reserve",     # cache: page reservation for the step's lines
    "step.build",       # scheduler: rows, BatchConfig, the key split
    "step.dispatch",    # engine: device_puts + the jitted step's call
    "step.flush",       # scheduler: the oldest step's host bookkeeping
    "step.flush_wait",  # engine: the blocking fetch inside the flush
)
# profiler names, built once: a span site builds no string per call
_ANNOTATION_NAMES = {name: "ff." + name for name in STEP_SPANS}
# inside ``step.reserve``, and only where the engine's pager has a class
# of page with a window: the host's freeing behind it
_ANNOTATION_NAMES["step.trim"] = "ff.step.trim"


#: The parts of one step program's build (obs/builds.py), as the trace
#: buffer names them: the Python trace of the program's function, the
#: jaxpr's lowering to MLIR (Mosaic's of each Pallas call with it) and
#: the backend's compile or the compilation cache's load. Keyed by the
#: part's name in a build's record.
BUILD_SPANS = {
    "trace": "build.trace",
    "lower": "build.lower",
    "backend": "build.backend",
}
#: ... and the one annotation on the profiler's clock, round the traced
#: function (``InferenceEngine._jit``'s wrapper: trace time only), with
#: the program's name as its ``program`` argument.
BUILD_ANNOTATION = "ff.build.trace"


def _annotation(name: str) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(
        _ANNOTATION_NAMES.get(name) or "ff." + name
    )


def _zero() -> int:
    return 0


class NullTracer:
    """The disabled tracer: ``enabled`` is False and stays False.

    Event sites check ``tracer.enabled`` BEFORE building event
    arguments, so on the hot path a disabled run costs one attribute
    read and one branch — ``event`` exists only so that an unguarded
    call is still safe (and so tests can monkeypatch it to raise,
    proving the guards hold). ``span`` is the bare profiler annotation:
    nothing is buffered, and nothing is recorded at all unless a
    profiler session is open."""

    __slots__ = ()
    enabled = False
    lane = ""

    def event(self, name: str, **kw: Any) -> None:
        return None

    def span(self, name: str, **kw: Any) -> jax.profiler.TraceAnnotation:
        return _annotation(name)

#: The process-wide disabled tracer every serve component starts with.
NULL_TRACER = NullTracer()


class TraceBuffer:
    """The run's event store (append-only, bounded).

    ``capacity`` bounds host memory on long runs: past it the oldest
    events drop and ``dropped`` counts them — an export of a bounded
    buffer says how much history it lost instead of silently
    truncating."""

    def __init__(self, capacity: int = 200_000):
        self.capacity = int(capacity)
        self.events: List[Dict[str, Any]] = []
        self.dropped = 0
        #: optional FlightRecorder observing every append
        self.recorder = None

    @property
    def enabled(self) -> bool:
        return True

    def append(self, ev: Dict[str, Any]) -> None:
        self.events.append(ev)
        if len(self.events) > self.capacity:
            overflow = len(self.events) - self.capacity
            del self.events[:overflow]
            self.dropped += overflow
        rec = self.recorder
        if rec is not None:
            rec.observe(ev)

    def extend(self, events, lane: Optional[str] = None) -> None:
        """Merge events shipped from another buffer (a remote replica's
        envelope). ``lane`` re-tags them when the shipper did not know
        its cluster lane; events are appended one by one so the flight
        recorder observes each."""
        for ev in events:
            if lane is not None and not ev.get("lane"):
                ev = dict(ev)
                ev["lane"] = lane
            self.append(ev)

    def drain(self) -> List[Dict[str, Any]]:
        """Take (and clear) the buffered events — how a replica server
        ships its spans home inside the RPC envelope."""
        out = self.events
        self.events = []
        return out

    def tracer(self, lane: str, clock: Optional[Callable[[], int]] = None
               ) -> "Tracer":
        """A per-lane recording view over this buffer."""
        return Tracer(self, lane, clock)


class Tracer:
    """A lane-tagged, clock-bound view over a :class:`TraceBuffer`.

    ``clock`` is the DETERMINISTIC half of the dual clock — a zero-arg
    callable returning the owner's step counter (scheduler steps for a
    RequestManager, cluster steps for the ClusterManager, client-side
    RPC steps for a RemoteReplica). Wall time is stamped alongside on
    every event.
    """

    __slots__ = ("buffer", "lane", "clock")

    enabled = True

    def __init__(self, buffer: TraceBuffer, lane: str,
                 clock: Optional[Callable[[], int]] = None):
        self.buffer = buffer
        self.lane = lane
        self.clock = clock or _zero

    def event(
        self,
        name: str,
        *,
        trace_id: int = -1,
        dur: float = 0.0,
        t: Optional[float] = None,
        step: Optional[int] = None,
        lane: Optional[str] = None,
        **attrs: Any,
    ) -> None:
        """Record one instant (``dur`` 0) or completed span. ``attrs``
        must be codec-safe primitives — they ride RPC envelopes and the
        JSON exports verbatim."""
        ev: Dict[str, Any] = {
            "name": name,
            "lane": self.lane if lane is None else lane,
            "trace_id": int(trace_id),
            "t": time.perf_counter() if t is None else t,
            "step": self.clock() if step is None else int(step),
            "dur": float(dur),
        }
        if attrs:
            ev["attrs"] = attrs
        self.buffer.append(ev)

    def span(self, name: str, *, trace_id: int = -1,
             lane: Optional[str] = None, **attrs: Any) -> "_Span":
        """Context manager: the same ``ff.<name>`` profiler annotation
        the null tracer returns, and on exit ``name`` recorded into the
        buffer with its measured wall duration (step stamped at ENTRY —
        the deterministic clock of a span is when it began)."""
        return _Span(self, name, trace_id, lane, attrs)


class _Span:
    __slots__ = ("_tr", "_name", "_tid", "_lane", "_attrs", "_t0", "_s0",
                 "_annotation")

    def __init__(self, tracer: Tracer, name: str, trace_id: int,
                 lane: Optional[str], attrs: Dict[str, Any]):
        self._tr = tracer
        self._name = name
        self._tid = trace_id
        self._lane = lane
        self._attrs = attrs
        self._annotation = _annotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        self._s0 = self._tr.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._annotation.__exit__(exc_type, exc, tb)
        self._tr.event(
            self._name,
            trace_id=self._tid,
            t=self._t0,
            dur=time.perf_counter() - self._t0,
            step=self._s0,
            lane=self._lane,
            **(
                dict(self._attrs, error=type(exc).__name__)
                if exc_type is not None else self._attrs
            ),
        )
        return False

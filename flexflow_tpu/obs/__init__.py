"""flexflow_tpu.obs — cluster-wide request tracing, metrics export and
the failure flight recorder.

The observability layer the reference ships in three pieces —
per-op ``--profiling`` timing, per-request ``ProfileInfo``, Legion Prof
timeline captures — rebuilt TPU-native over the serve stack:

* :mod:`.tracer` — a low-overhead span/event recorder with a DUAL
  clock: wall time for humans/exports, deterministic scheduler/cluster
  step counts for tests. Request-lifecycle spans (admit →
  prefix_lookup → prefill_chunk* → decode/mixed steps → spec
  draft/verify → migrate → flush/terminal) flow from the
  RequestManager, the engine's dispatch chokepoint, SpecInfer and the
  ClusterManager; RPC retries, heartbeat gaps and health transitions
  become events too. Disabled (the default, :data:`NULL_TRACER`) the
  layer costs one attribute read per event site — proven free in
  tests. The six phases of a scheduler step (``tracer.STEP_SPANS``:
  admit, reserve, build, dispatch, flush, flush_wait) go through the
  one span primitive ``tracer.span(name)``, which is a
  ``jax.profiler.TraceAnnotation("ff.<name>")`` on EITHER tracer: an
  xprof capture of a serving process shows them beside the device's
  operations with no set-up (the profiler session is the switch), and
  an attached tracer also buffers them with a duration. The buffer's
  wall stamps are ``time.perf_counter()``, the clock ``ProfileInfo``
  uses, so a request's events and stamps compare; the profiler's
  clock is the session's own and only annotations can be put on it.
* :mod:`.sublayers` — beneath the jit boundary: the one vocabulary of
  ``ff.*`` named scopes that every step program carries
  (``sublayer(name)``; metadata, nothing at dispatch), so an xprof
  capture groups the device's operations by sublayer, and
  ``scope_maps()``, which reads ``{program: {HLO instruction:
  sublayer}}`` off the compiled executables for a reader that has only
  instruction names (the benchmark's ``step.sub_ms.*``).
* :mod:`.builds` — before a program can be dispatched at all: the
  build log. One set of ``jax.monitoring`` listeners, registered at
  the first engine's construction, files what each step program's
  build cost (Python trace, lowering, backend compile or cache load)
  under the program's name: a record a build on the engine and in
  ``SchedulerStats.builds``, the ``build_*`` counters (and
  ``compiles`` / ``retraces``, with or without a sanitizer),
  ``ff.build.trace`` on the profiler's clock and ``build.trace`` /
  ``build.lower`` / ``build.backend`` in an attached buffer — the
  engine's lane of the Chrome export shows a build between the
  requests it delayed. Its wrapper runs when a program is traced and
  its listener when one is built; a dispatch runs neither.
* :mod:`.export` — Chrome/Perfetto ``trace_event`` JSON (one lane per
  replica; a migrated request is ONE trace id hopping lanes) and a
  Prometheus text snapshot mechanically derived from
  ``SchedulerStats``/``ClusterStats``/``ProfileInfo`` with a drift
  guard asserting every counter is exported or explicitly excluded.
* :mod:`.flight_recorder` — a bounded per-lane ring of recent events
  that auto-dumps a REDACTED post-mortem on health-machine DOWN trips,
  failover errors and terminal request errors.

Cross-host correlation: a trace id is bound per request at submission
and rides the PR-12 RPC envelope (``serve/cluster/{remote,server}.py``)
— a replica server traces into its own buffer and ships the events
home inside every state-bearing response, so the front-end stitches
router + prefill replica + wire hop + decode replica into ONE timeline
even across processes.

Entry points: :func:`attach_observability` wires a tracer (and
optionally a recorder) onto a RequestManager / Replica /
ClusterManager; the CLI exposes ``--trace-out`` / ``--metrics-out`` /
``--flight-recorder`` on ``flexflow_tpu serve``.
"""
from __future__ import annotations

from typing import Optional

from .export import (
    ExportDriftError,
    check_export_coverage,
    chrome_trace,
    prometheus_text,
    write_chrome_trace,
    write_prometheus,
)
from .builds import BuildLog
from .flight_recorder import REDACTED_ATTRS, FlightRecorder
from .sublayers import SUBLAYERS, scope_maps, sublayer
from .tracer import NULL_TRACER, NullTracer, TraceBuffer, Tracer

__all__ = [
    "TraceBuffer",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "FlightRecorder",
    "BuildLog",
    "REDACTED_ATTRS",
    "chrome_trace",
    "write_chrome_trace",
    "prometheus_text",
    "write_prometheus",
    "check_export_coverage",
    "ExportDriftError",
    "attach_observability",
    "SUBLAYERS",
    "sublayer",
    "scope_maps",
]


def _attach_rm(rm, buffer: TraceBuffer, lane: str, recorder) -> None:
    """Wire one scheduler (RequestManager or SpecInferManager): the
    manager and every engine it keeps in sync share ONE lane-tagged
    tracer whose deterministic clock is the scheduler step counter."""
    tr = buffer.tracer(lane, clock=lambda: rm._step_counter)
    rm.tracer = tr
    rm.flight_recorder = recorder
    for eng in rm._engines():
        eng.tracer = tr


def _attach_replica(rep, buffer: TraceBuffer, recorder) -> None:
    lane = f"replica{rep.index}"
    if getattr(rep, "is_remote", False):
        # the client-side view traces the WIRE (rpc spans, retries) on
        # its own lane, clocked by the replica's client-side step
        # counter; the server-side scheduler traces into its OWN buffer
        # and its events come home inside RPC envelopes, already tagged
        # with the replica lane (loopback: the wrapped local replica;
        # socket: the subprocess enables tracing via its spec).
        rep.tracer = buffer.tracer(
            "wire", clock=lambda rep=rep: rep.steps_taken
        )
        transport = getattr(rep, "transport", None)
        if transport is not None:
            transport.tracer = rep.tracer
        if rep.local is not None:
            _attach_rm(rep.local.rm, TraceBuffer(), lane, None)
    else:
        _attach_rm(rep.rm, buffer, lane, recorder)


def attach_observability(
    target,
    *,
    buffer: Optional[TraceBuffer] = None,
    recorder: Optional[FlightRecorder] = None,
    capacity: int = 200_000,
) -> TraceBuffer:
    """Enable tracing on ``target`` — a ClusterManager, a Replica, or a
    bare RequestManager/SpecInferManager — and return the
    :class:`TraceBuffer` that collects the run's events (export it with
    :func:`write_chrome_trace` / :func:`prometheus_text`). ``recorder``
    additionally arms the flight recorder's per-lane ring + dump
    triggers. Duck-typed so :mod:`flexflow_tpu.serve` never imports
    this package on its hot path."""
    if buffer is None:
        buffer = TraceBuffer(capacity)
    if recorder is not None:
        buffer.recorder = recorder
    if hasattr(target, "replicas") and hasattr(target, "router"):
        # ClusterManager: the router/manager lane runs on cluster steps
        target.tracer = buffer.tracer(
            "router", clock=lambda: target._step_counter
        )
        target.flight_recorder = recorder
        for rep in list(target.replicas) + list(
            getattr(target, "standbys", ())
        ):
            _attach_replica(rep, buffer, recorder)
        return buffer
    if hasattr(target, "rm") and hasattr(target, "index"):
        _attach_replica(target, buffer, recorder)
        return buffer
    if hasattr(target, "engine") and hasattr(target, "_engines"):
        _attach_rm(target, buffer, "engine", recorder)
        return buffer
    raise TypeError(
        f"attach_observability: unsupported target {type(target).__name__}"
        " (expected a ClusterManager, Replica, or RequestManager)"
    )

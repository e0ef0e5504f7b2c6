"""The build log — what each step program's build cost and where the
time went, by the program's NAME, recorded inside the program.

A serving process pays for a program three times before it can
dispatch it: the Python trace of its function, the jaxpr's lowering to
MLIR (Mosaic's lowering of each Pallas call with it), and the
backend's compile — or, where JAX's persistent compilation cache has
the executable, its load. JAX measures each at the place it happens
and says so through ``jax.monitoring``: the duration events
``/jax/core/compile/jaxpr_trace_duration`` (``fun_name`` the traced
function's name), ``.../jaxpr_to_mlir_module_duration`` and
``.../backend_compile_duration`` (both ``fun_name='jit(<name>)'``)
fire once a build, in that order, on the thread that builds; a scalar
event of the same name fires as each part BEGINS; the nameless
``/jax/compilation_cache/*`` events fire inside the backend part of
the build they belong to. ``InferenceEngine._jit`` names every step
program from its key (``serve/engine.program_name``), so an event is
put down to its program with no guess.

This module is the ONE place that hears them. It registers its
listeners once a process, at the first engine's construction
(:class:`BuildLog`) and never at import; each engine owns a
:class:`BuildLog`, ``_jit``'s wrapper — which runs when a program is
traced and never at a dispatch — opens a :class:`Build` in it
(:meth:`BuildLog.tracing`), and the listener files the parts that
follow on that thread under it. So:

* a **record** a build (``BuildLog.records``, and
  ``SchedulerStats.builds`` where a scheduler drives the engine): the
  key, the build's ordinal for its name (over 1: a retrace, filed as
  ``<name>#<ordinal>``), ``trace_s`` / ``lower_s`` / ``backend_s``,
  what the compilation cache said (``hit``, ``miss`` — it was asked
  and had nothing — or ``off``: not asked, or no directory), a hit's ``cache_load_s`` and
  ``saved_s`` (what the compile cost when it was paid), the start on
  ``time.perf_counter()`` (the clock of ``ProfileInfo`` and the trace
  buffer), the scheduler step it began in, whether a request was live
  then, and ``inner``: the jitted functions traced while the program's
  own trace was open, by name with count and seconds. They are PART of
  ``trace_s`` (``inner_s`` sums those the program called itself, which
  do not overlap); ``trace_s - inner_s`` is the program's own Python.
  A bare ``pl.pallas_call`` shows among them as ``wrapped`` (what jax
  0.9.0 jits round a kernel's call), once a call site;
* **counters** on ``SchedulerStats`` (``note_build``): ``compiles`` /
  ``retraces`` — counted here and nowhere else, with or without a
  sanitizer — ``build_trace_s`` / ``build_lower_s`` /
  ``build_backend_s``, ``build_cache_hits`` / ``build_cache_misses``,
  ``build_in_step_s`` and ``build_other_s``;
* **spans**: ``ff.build.trace`` on the profiler's clock round the
  traced function, and ``build.trace`` / ``build.lower`` /
  ``build.backend`` in an attached tracer's buffer (and so in the
  Chrome export's engine lane and the flight recorder's ring);
* every build the engine's ``_jit`` did NOT hand out (a harness's
  reference, ``jnp`` helpers run outside any program) summed into one
  ``other`` record, so the log accounts for the process from the first
  engine on.

A second lowering of a program that is already built
(``InferenceEngine.step_program_texts``: the tracing cache answers, the
wrapper does not run) is no build: it adds no record, no ordinal and
nothing to a counter.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from .tracer import BUILD_ANNOTATION, BUILD_SPANS, NULL_TRACER

__all__ = ["BuildLog", "Build", "OTHER"]

#: the record every build that is no step program's is summed into
OTHER = "other"

_PARTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
# heard between a backend part's start and its end, nameless: the cache
# was asked / had the executable / had not and was given it
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "load_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}


class _Thread(threading.local):
    """What the listener keeps between the events of one thread: a
    build's parts follow one another on the thread that builds."""

    def __init__(self):
        self.build: Optional[Build] = None  # opened by a wrapper, not yet compiled
        self.depth = 0                      # parts open round the next event
        self.cache: Dict[str, Any] = {}     # the cache's word since the last backend part


_T = _Thread()
_LOGS: "weakref.WeakSet[BuildLog]" = weakref.WeakSet()
_NAMES: set = set()          # every program name a wrapper has run under
_registered = False


@dataclasses.dataclass
class Build:
    """One build of one step program (module docstring)."""

    name: str
    key: Any
    start: float
    step: int
    in_step: bool
    ordinal: int = 0
    trace_s: float = 0.0
    lower_s: float = 0.0
    backend_s: float = 0.0
    cache: str = "off"
    cache_load_s: float = 0.0
    saved_s: float = 0.0
    inner_s: float = 0.0
    inner: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    # the listener's own: whose it is, where its parts go, and how deep
    # the program's trace sits among the thread's open parts (what it
    # calls itself sits one deeper: ``inner_s``)
    label: str = ""
    log: Any = None
    tracer: Any = NULL_TRACER
    depth: int = 1
    running: bool = True     # the wrapper is on the stack
    tracing: bool = True     # the program's own trace part has not ended

    def numbers(self) -> Dict[str, Any]:
        """The record as codec-safe primitives (``SchedulerStats.builds``)."""
        return {
            "key": repr(self.key), "ordinal": self.ordinal,
            "trace_s": self.trace_s, "lower_s": self.lower_s,
            "backend_s": self.backend_s, "cache": self.cache,
            "cache_load_s": self.cache_load_s, "saved_s": self.saved_s,
            "start": self.start, "step": self.step, "in_step": self.in_step,
            "inner_s": self.inner_s,
            "inner": {n: list(v) for n, v in self.inner.items()},
        }


def _cache_word(cache: Dict[str, Any]) -> str:
    """``hit``; ``miss``, where the cache wrote the executable or was
    asked, has a directory and had nothing (a program it will not keep,
    one that compiles under its time threshold, misses every run);
    ``off`` where it was not asked or has nowhere to look."""
    if cache.get("hit"):
        return "hit"
    if cache.get("miss") or (
            cache.get("asked") and jax.config.jax_compilation_cache_dir):
        return "miss"
    return "off"


def _no_scheduler() -> Tuple[Any, int, bool]:
    """An engine nothing drives: no stats, no step, no request."""
    return None, -1, False


class BuildLog:
    """One engine's builds. ``scheduler`` (wired by the RequestManager
    that drives the engine, :meth:`attach`) is ``() -> (stats, step,
    live)``: where the counters go, the scheduler's step counter and
    whether it holds a live request — every dispatch of a driven engine
    is made from ``RequestManager.step``, so a build that begins while
    one is live began inside a step and delayed it."""

    def __init__(self):
        _register()
        self.records: Dict[str, Build] = {}
        self.other: Dict[str, Any] = {
            "count": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache_hits": 0, "cache_misses": 0}
        self._ordinals: Dict[str, int] = {}
        self.scheduler: Callable[[], Tuple[Any, int, bool]] = _no_scheduler
        self.prefix = ""
        _LOGS.add(self)

    def attach(self, scheduler, prefix: str = "") -> None:
        """``prefix`` tells a second engine's records from the first's
        in one scheduler's stats (a SpecInfer server's draft engines
        build programs of the same names)."""
        self.scheduler = scheduler
        self.prefix = prefix

    @contextlib.contextmanager
    def tracing(self, name: str, key: Any, tracer=NULL_TRACER):
        """Round the traced function, in ``_jit``'s wrapper: opens the
        build that the thread's next parts belong to, under the
        ``ff.build.trace`` annotation, and counts it once the function
        has returned — a trace that raises (the retrace sentinel's
        refusal, a shape error) is no build."""
        t = _T
        if t.build is not None and t.build.running:
            yield        # a program traced inside a program: its ``inner``
            return
        _, step, live = self.scheduler()
        build = t.build = Build(
            name=name, key=key, start=time.perf_counter(), step=step,
            in_step=live, log=self, tracer=tracer, depth=max(t.depth, 1))
        _NAMES.add(name)
        try:
            with jax.profiler.TraceAnnotation(BUILD_ANNOTATION, program=name):
                yield
        except BaseException:
            t.build = None
            raise
        finally:
            build.running = False
        n = self._ordinals[name] = self._ordinals.get(name, 0) + 1
        build.ordinal = n
        build.label = self.prefix + (name if n == 1 else f"{name}#{n}")
        self.records[build.label] = build
        self._note(build.label, build.numbers(), "begin", 0.0)

    def _note(self, label, numbers, part, seconds):
        stats = self.scheduler()[0]
        if stats is not None:
            stats.note_build(label, numbers, part, seconds)

    def _part(self, build: Build, part: str, seconds: float,
              cache: Optional[Dict[str, Any]] = None) -> None:
        if part == "trace":
            build.tracing = False
            build.trace_s = seconds
        elif part == "lower":
            build.lower_s += seconds
        else:
            build.backend_s += seconds
            build.cache = _cache_word(cache)
            build.cache_load_s = cache.get("load_s", 0.0)
            build.saved_s = cache.get("saved_s", 0.0)
        tr = build.tracer
        if tr.enabled:
            attrs = {"program": build.label, "ordinal": build.ordinal}
            if part == "backend":
                attrs["cache"] = build.cache
            tr.event(BUILD_SPANS[part], t=time.perf_counter() - seconds,
                     dur=seconds, step=build.step, **attrs)
        self._note(build.label, build.numbers(), part, seconds)

    def _other(self, part: str, seconds: float,
               cache: Optional[Dict[str, Any]]) -> None:
        o = self.other
        o[part + "_s"] += seconds
        if part == "backend":
            o["count"] += 1
            word = _cache_word(cache)
            if word != "off":
                o["cache_hits" if word == "hit" else "cache_misses"] += 1
        self._note(OTHER, dict(o), part, seconds)


def _on_scalar(event: str, value, **kw) -> None:
    if event in _PARTS:          # a part begins on this thread
        _T.depth += 1


def _on_event(event: str, **kw) -> None:
    word = _CACHE_EVENTS.get(event)
    if word is not None:
        _T.cache[word] = True


def _on_duration(event: str, seconds: float, **kw) -> None:
    part = _PARTS.get(event)
    if part is None:
        slot = _CACHE_SECONDS.get(event)
        if slot is not None:
            _T.cache[slot] = seconds
        return
    t = _T
    depth = max(t.depth, 1)      # this part's own; 1: none is open round it
    t.depth = depth - 1
    fun = kw.get("fun_name", "")
    build = t.build
    if build is not None and build.tracing:
        if part == "trace" and fun == build.name and not build.running:
            build.log._part(build, part, seconds)
        else:
            # traced while the program's trace is open: part of it
            rec = build.inner.get(fun)
            if rec is None:
                build.inner[fun] = [1, seconds]
            else:
                rec[0] += 1
                rec[1] += seconds
            if depth == build.depth + 1:
                build.inner_s += seconds
        return
    cache = None
    if part == "backend":
        cache, t.cache = t.cache, {}
    # 'jit(ff_step_c1)' -> 'ff_step_c1'
    name = fun[fun.find("(") + 1:-1] if fun.endswith(")") else fun
    if build is not None and part != "trace" and name == build.name:
        build.log._part(build, part, seconds, cache)
        if part == "backend":
            t.build = None
        return
    # inside a part that is counted whole, or a built program lowered
    # again (step_program_texts): nothing
    if depth > 1 or name in _NAMES:
        return
    for log in list(_LOGS):
        log._other(part, seconds, cache)


def _register() -> None:
    """Once a process, at the first engine's construction."""
    global _registered
    if _registered:
        return
    _registered = True
    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

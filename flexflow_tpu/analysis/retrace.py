"""Retrace sentinel — compile-event accounting for the serving engine.

The InferenceEngine's whole performance contract is "one compiled
program per static step signature, replayed forever" (engine._get_step
and friends). The silent killer is the *retrace*: a host-side change —
a weak dtype flipping, a page-table shape drifting, an int that used to
be an np.int32 arriving as a Python int — gives an existing step key a
NEW abstract signature, and XLA quietly recompiles. On CPU tests that
costs milliseconds and nobody notices; on a TPU pod it is a 100x
step-latency spike in production.

:class:`RetraceGuard` hooks the engine's jit chokepoint
(``InferenceEngine._jit`` — every entry in ``engine._steps`` plus
``_commit``/``copy_page``/``reorder`` is created through it): the
function handed to ``jax.jit`` is wrapped so that each *trace* (which
is exactly one compile) records a :class:`CompileEvent` with the step
key, the abstract ``(shape, dtype, weak_type)`` signature of every
argument, and the cumulative per-key count. In strict mode a second
compile for the same key raises :class:`RetraceError` at the dispatch
that caused it — the shape/dtype-drift bug class fails in tests instead
of shipping. ``seal()`` additionally forbids compiles of *new* keys
(full steady-state assertion for benches).

Enable via ``ServingConfig(sanitizers=("retrace",))`` (strict) or
``("retrace-warn",)`` (record + log only), or ``FF_SANITIZERS=retrace``
in the environment. Compile events are logged at
``FF_LOG=serve=debug``. The guard keeps what only it does — the
signatures, ``strict`` and ``seal``: ``SchedulerStats.compiles`` /
``retraces`` are counted by the engine's build log (obs/builds.py) at
the same chokepoint, whether or not a guard is set, and a trace the
guard refuses never reaches that log.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional

from ..logging_utils import get_logger


class RetraceError(RuntimeError):
    """A jitted step recompiled (or, sealed, compiled anew) after it was
    supposed to be steady-state."""


def abstract_signature(args: tuple, kwargs: dict) -> str:
    """Stable string of the abstract (shape/dtype/weak_type) signature
    of a call — the part of jax's cache key that retraces key on.
    Works on tracers (during trace) and concrete arrays alike."""
    import jax

    leaves, treedef = jax.tree.flatten((args, kwargs))
    parts: List[str] = []
    for leaf in leaves:
        aval = getattr(leaf, "aval", None)
        if aval is not None:
            # ShapedArray repr includes weak_type when set
            parts.append(repr(aval))
        elif hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            parts.append(
                f"{leaf.dtype}[{','.join(str(d) for d in leaf.shape)}]"
            )
        else:
            parts.append(f"{type(leaf).__name__}:{leaf!r}")
    return f"{treedef} :: " + ", ".join(parts)


@dataclasses.dataclass(frozen=True)
class CompileEvent:
    """One lowering/compile of one step program."""

    key: Any           # engine step key, e.g. (1, False, False)
    signature: str     # abstract signature of the traced call
    count: int         # cumulative compiles for this key (1 = first)
    seq: int           # global compile ordinal across all keys


class RetraceGuard:
    """Records every compile of every instrumented step program; in
    strict mode a recompile raises at the offending dispatch."""

    def __init__(self, strict: bool = True):
        self.strict = strict
        self.compiles: Dict[Any, List[str]] = {}
        self.events: List[CompileEvent] = []
        self.retraces = 0
        self._sealed = False
        self._log = get_logger("serve")

    # -- engine integration ------------------------------------------------

    def instrument(self, fn: Callable, key: Any) -> Callable:
        """Wrap a to-be-jitted function so each trace (= compile) is
        recorded under ``key`` before tracing proceeds. The wrapper
        preserves positional arguments, so ``donate_argnums`` indices
        are unchanged, and keeps the function's name, which is the
        program's name in HLO and in a profile (``jit_<name>``)."""

        @functools.wraps(fn)
        def traced(*args, **kwargs):
            self.record(key, args, kwargs)
            return fn(*args, **kwargs)

        return traced

    def record(self, key: Any, args: tuple = (), kwargs: Optional[dict] = None):
        sig = abstract_signature(args, kwargs or {})
        prev = self.compiles.setdefault(key, [])
        is_retrace = bool(prev)
        prev.append(sig)
        event = CompileEvent(
            key=key, signature=sig, count=len(prev), seq=len(self.events)
        )
        self.events.append(event)
        self._log.debug(
            "compile key=%r count=%d sig=%s", key, event.count, sig
        )
        if is_retrace:
            self.retraces += 1
            if self.strict or self._sealed:
                raise RetraceError(
                    f"step {key!r} RECOMPILED (compile #{len(prev)}): the "
                    f"abstract signature drifted.\n  first:  {prev[0]}\n"
                    f"  now:    {sig}\n"
                    "A host-side shape/dtype/weak-type changed between "
                    "dispatches of the same step key — on TPU this is a "
                    "silent 100x step-latency spike."
                )
        elif self._sealed:
            # the trace aborts here — un-record it so an unseal()+retry
            # is a first compile, not a phantom recompile
            prev.pop()
            if not prev:
                self.compiles.pop(key, None)
            self.events.pop()
            raise RetraceError(
                f"NEW step key {key!r} compiled after seal(): sig={sig}. "
                "Steady state was declared (seal()) but this dispatch "
                "still needed a fresh program."
            )

    # -- assertions / reporting -------------------------------------------

    def seal(self):
        """Declare steady state: any further compile — same key or new —
        raises. Call after warmup in benches."""
        self._sealed = True

    def unseal(self):
        self._sealed = False

    def reset(self):
        """Forget all recorded compiles (e.g. after an engine.reset())."""
        self.compiles.clear()
        self.events.clear()
        self.retraces = 0
        self._sealed = False

    @property
    def total_compiles(self) -> int:
        return len(self.events)

    def compile_counts(self) -> Dict[Any, int]:
        """{step key: number of compiles}. Steady-state healthy = every
        value is exactly 1."""
        return {k: len(v) for k, v in self.compiles.items()}

    def assert_one_compile_per_key(self):
        """The churn-test invariant: every step key compiled exactly
        once over the guarded run."""
        bad = {k: n for k, n in self.compile_counts().items() if n != 1}
        if bad:
            raise RetraceError(
                f"step keys recompiled (key -> compiles): {bad}; "
                f"signatures: "
                + "; ".join(
                    f"{k!r}: {self.compiles[k]}" for k in bad
                )
            )

"""RemoteReplica — the Replica surface over an RPC transport.

The cluster front-end (:class:`~.manager.ClusterManager`, the
:class:`~.router.Router`, :mod:`.migration`) was deliberately written
against the Replica surface; this module makes a replica living behind
a :class:`~.transport.Transport` (in-process loopback, or a subprocess
TCP server) look exactly like the in-process one:

* **Every RPC gets a deadline, bounded retries and exponential
  backoff** (:meth:`RemoteReplica._rpc` — ``ServingConfig.
  rpc_deadline_s`` / ``rpc_retries`` / ``rpc_backoff_s``). Retries
  reuse the request's ``seq``, so the server's response cache makes a
  retried ``step``/``submit`` at-most-once even when only the RESPONSE
  was lost. A call that exhausts its retries raises the final
  :class:`~.transport.TransportError` to the caller — the manager's
  drive loop feeds it to the SAME HealthMonitor machine a local step
  exception feeds (``rpc_errors`` counted in ClusterStats).
* **Heartbeats carry the SchedulerStats the queue-delay estimates
  read.** Every state-bearing response (step/heartbeat/drain/submit)
  piggybacks an envelope — telemetry + per-request flushed state — and
  the client keeps a MIRROR: ``rm.requests[rid]`` are
  :class:`_RequestView` objects holding flushed tokens/status/error,
  ``rm.stats`` replays the last ``SchedulerStats`` snapshot, and
  ``load()``/``backlog_tokens()`` are computed client-side from the
  mirror (the same inputs the in-process estimate reads). The mirror
  only ever holds FLUSHED truth — which is exactly what failover
  re-admission needs, and why ``_on_replica_down`` works even when the
  transport to the dead replica is gone.
* **Heartbeat gaps are counted in deterministic cluster steps**, never
  wall clock: the manager stamps ``last_contact_step`` on every
  successful exchange and raises ONE gap observation per cluster step
  once ``heartbeat_gap_steps`` elapse without contact — preserving
  PR-9's no-wall-clock transition contract (and its threshold
  arithmetic: a replica that is simultaneously gapped and erroring is
  observed once per step, never twice).
* **Fault injection is client-side**, at the same two seams the
  in-process cluster uses: ``FaultPlan`` replica kinds
  (crash/transient/latency/oom) fire at the top of :meth:`step`
  exactly like ``Replica.step`` does, and the transport kinds
  (drop/delay/disconnect/partition) are consulted per RPC attempt in
  :meth:`_rpc` — so PR-9's deterministic chaos machinery transfers to
  the wire unchanged.

Profile mirroring: the CLIENT owns the authoritative
:class:`ProfileInfo` (it is what ``ClusterManager.result`` returns).
Server-side counter fields merge in as deltas over a per-home base —
so a request that failed over accumulates ``llm_decoding_steps``
across homes exactly like the in-process shared-object flow — while
client-owned routing fields (``replica_id``, ``retries``,
``transport_retries``…) are never touched by a merge.
"""
from __future__ import annotations

import dataclasses
import itertools
import random
import time
from typing import Any, Dict, List, Optional, Sequence, Union

from ...logging_utils import get_logger
from ...obs.tracer import NULL_TRACER
from ..batch_config import GenerationConfig, ProfileInfo
from ..request_manager import TERMINAL_STATUSES, RequestStatus
from .server import gen_to_wire
from .transport import (
    _STATS_LOCK,
    RemoteError,
    RpcFuture,
    Transport,
    TransportError,
)


class _AsyncCall:
    """One logical RPC in flight: the seq is assigned and attempt 0
    issued (without blocking on the response) at CONSTRUCTION — the
    call site in the concurrent drive loop is where the serial loop
    would have blocked. :meth:`wait` harvests the response, and on the
    rare failure path drives attempts 1..N SYNCHRONOUSLY with exactly
    the serial ``_rpc`` semantics — per-attempt fault consults, seq
    reuse (the server's response cache keeps retries at-most-once),
    ``rpc_retries``/``rpc_errors`` accounting, exponential backoff on
    real links, ``rpc_retry``/``rpc`` tracer events. The sync ``_rpc``
    is literally ``_AsyncCall(...).wait()``, so there is ONE retry
    state machine for both drive loops."""

    __slots__ = ("owner", "method", "args", "seq", "deadline", "retries",
                 "retries_spent", "t0", "completed_at", "future",
                 "_pre_exc")

    def __init__(self, owner: "RemoteReplica", method: str,
                 args: Dict[str, Any], retryable: bool = True):
        self.owner = owner
        self.method = method
        self.args = args
        self.seq = next(owner._seq)  # ONE seq per logical call, reused
        # across retries — the server's response cache de-duplicates
        self.deadline = owner.serving.rpc_deadline_s
        self.retries = owner.serving.rpc_retries if retryable else 0
        self.retries_spent = 0
        self.t0 = time.perf_counter() if owner.tracer.enabled else 0.0
        #: perf_counter stamp of the final successful attempt's
        #: completion (set by the transport's resolving thread for the
        #: in-flight fast path) — the manager derives RTT from it
        self.completed_at: Optional[float] = None
        self.future: Optional[RpcFuture] = None
        self._pre_exc: Optional[TransportError] = None
        try:
            self._consult_faults(attempt=0)
        except TransportError as exc:
            # the injected fault consumed attempt 0 WITHOUT touching
            # the transport — wait() resumes at attempt 1, exactly the
            # serial loop's flow
            self._pre_exc = exc
            return
        self.future = owner.transport.call_async(
            self.seq, method, args, self.deadline
        )

    def _consult_faults(self, attempt: int) -> None:
        owner = self.owner
        if owner.fault_injector is None:
            return
        extra = owner.fault_injector.on_rpc(
            owner.index, owner.steps_taken, self.method, attempt
        )
        if extra:
            if extra >= self.deadline:
                from .transport import DeadlineExceeded

                raise DeadlineExceeded(
                    f"injected delay {extra}s exceeds the "
                    f"{self.deadline}s rpc deadline ({self.method})"
                )
            # a slow-but-alive link: the health machine sees it as
            # step latency, same as the in-process "latency" fault kind
            owner.injected_latency_s += extra

    def wait(self) -> Any:
        """Harvest the response (or exhaust the retry budget and raise
        the final :class:`TransportError`; a :class:`RemoteError` —
        the server executed and raised — propagates immediately,
        never retried)."""
        owner = self.owner
        tr = owner.tracer
        owner._last_call_retries = 0
        last_exc: Optional[TransportError] = None
        if self._pre_exc is not None:
            last_exc = self._pre_exc
            self._note_attempt_failed(0, last_exc)
        else:
            try:
                result = self.future.result()
                self.completed_at = self.future.completed_at
                self._note_ok(attempts=1)
                return result
            except TransportError as exc:
                last_exc = exc
                self._note_attempt_failed(0, exc)
        for attempt in range(1, self.retries + 1):
            self.retries_spent += 1
            owner._last_call_retries += 1
            st = owner.stats
            if st is not None:
                # same lock as the transports' wire counters: a reader
                # thread mid-_count() must not interleave with this RMW
                with _STATS_LOCK:
                    st.rpc_retries += 1
            if tr.enabled:
                # retries/backoff are part of the request's wire
                # story — each is its own event on the wire lane
                tr.event(
                    "rpc_retry", method=self.method, attempt=attempt,
                    replica=owner.index,
                    error=type(last_exc).__name__,
                )
            if owner.transport.needs_backoff:
                # ffcheck: disable=FF109 -- retry backoff against a real socket peer is inherently wall-clock (the link recovers with time, not with steps); gated off for loopback via needs_backoff
                time.sleep(
                    owner.serving.rpc_backoff_s * (2 ** (attempt - 1))
                )
            try:
                self._consult_faults(attempt)
                result = owner.transport.call(
                    self.seq, self.method, self.args, self.deadline
                )
                self.completed_at = time.perf_counter()
                self._note_ok(attempts=attempt + 1)
                return result
            except TransportError as exc:
                last_exc = exc
                self._note_attempt_failed(attempt, exc)
                continue
        st = owner.stats
        if st is not None:
            with _STATS_LOCK:
                st.rpc_errors += 1
        assert last_exc is not None
        self.completed_at = time.perf_counter()
        if tr.enabled:
            tr.event(
                "rpc", t=self.t0, dur=time.perf_counter() - self.t0,
                method=self.method, replica=owner.index,
                attempts=self.retries + 1, ok=False,
                error=type(last_exc).__name__,
            )
        raise last_exc

    def _note_ok(self, attempts: int) -> None:
        owner = self.owner
        tr = owner.tracer
        if tr.enabled:
            tr.event(
                "rpc", t=self.t0, dur=time.perf_counter() - self.t0,
                method=self.method, replica=owner.index,
                attempts=attempts, ok=True,
            )

    def _note_attempt_failed(self, attempt: int,
                             exc: TransportError) -> None:
        owner = self.owner
        if getattr(exc, "kind", None) == "disconnect":
            owner.transport.drop_connection()
        owner._log.debug(
            "rpc %s to replica %d attempt %d failed: %s",
            self.method, owner.index, attempt, exc,
        )


class HeartbeatGap(RuntimeError):
    """No successful contact with a remote replica for
    ``heartbeat_gap_steps`` cluster steps — the manager feeds this to
    the health machine like a step failure (one observation per step)."""


#: ProfileInfo fields whose server-side values merge as DELTAS over the
#: per-home base (counters that must accumulate across failover homes).
_PROFILE_COUNTERS = (
    "llm_decoding_steps", "ssm_decoding_steps",
    "speculated_tokens", "accepted_tokens", "spec_rounds", "tree_resizes",
)
#: server-owned "latest state" fields — overwritten by each merge.
_PROFILE_LATEST = (
    "cached_prefix_len", "host_hit_tokens", "tree_width", "tree_depth",
    "context_shards",
)
#: stamps a request keeps from the FIRST home that set them
_PROFILE_FIRST_HOME = (
    "start_time", "admit_time", "prefill_dispatched_time",
    "first_token_time",
)


class _RequestView:
    """Client-side mirror of one remote request — Request-shaped for
    everything the manager reads (status/tokens/error/pipeline_refs)
    and writes (``profile``)."""

    __slots__ = ("request_id", "prompt", "tokens", "prompt_len", "n_sched",
                 "slot", "pipeline_refs", "status", "error",
                 "_profile", "_profile_base")

    def __init__(self, rid: int):
        self.request_id = rid
        self.prompt = ""
        self.tokens: List[int] = []
        self.prompt_len = 0
        self.n_sched = 0
        self.slot = -1
        self.pipeline_refs = 0
        self.status = RequestStatus.PENDING
        self.error: Optional[str] = None
        self._profile = ProfileInfo()
        self._profile_base = {}
        self._rebase()

    # profile replacement (failover re-admission binds the carried
    # cluster profile onto the new home's view) re-anchors the merge
    # base so the new home's counters ADD to the carried totals
    @property
    def profile(self) -> ProfileInfo:
        return self._profile

    @profile.setter
    def profile(self, value: ProfileInfo) -> None:
        self._profile = value
        self._rebase()

    def _rebase(self) -> None:
        self._profile_base = {
            f: getattr(self._profile, f) for f in _PROFILE_COUNTERS
        }
        for f in _PROFILE_FIRST_HOME:
            self._profile_base[f] = getattr(self._profile, f)

    @property
    def output_tokens(self) -> List[int]:
        return self.tokens[self.prompt_len:]

    def apply(self, state: Dict[str, Any]) -> None:
        self.tokens = [int(t) for t in state["tokens"]]
        self.prompt_len = int(state["prompt_len"])
        self.n_sched = int(state["n_sched"])
        self.slot = int(state["slot"])
        self.pipeline_refs = int(state["pipeline_refs"])
        self.status = RequestStatus(state["status"])
        self.error = state["error"]
        prof = state.get("profile")
        if prof:
            self._merge_profile(prof)

    def _merge_profile(self, server: Dict[str, Any]) -> None:
        p, base = self._profile, self._profile_base
        for f in _PROFILE_COUNTERS:
            setattr(p, f, base[f] + int(server.get(f, 0)))
        for f in _PROFILE_LATEST:
            if server.get(f):
                setattr(p, f, server[f])
        # times: the FIRST home's start/admit/dispatch/first-token
        # stamps win; finish follows the latest home
        for f in _PROFILE_FIRST_HOME:
            if not base[f] and server.get(f):
                setattr(p, f, server[f])
        if server.get("finish_time"):
            p.finish_time = server["finish_time"]


class _RemoteStats:
    """SchedulerStats-shaped replay of the last heartbeat snapshot:
    ``snapshot()`` feeds ClusterStats aggregation unchanged, and
    counter reads (``stats.retraces`` …) resolve against the snapshot.
    Zero until the first envelope (or after a bench-style stat swap —
    counting resumes at the next heartbeat's snapshot)."""

    def __init__(self):
        self._snap: Dict[str, Any] = {}

    def update(self, snap: Dict[str, Any]) -> None:
        self._snap = dict(snap)

    def snapshot(self) -> Dict[str, Any]:
        return dict(self._snap)

    def __getattr__(self, name):
        snap = object.__getattribute__(self, "_snap")
        if name in snap:
            return snap[name]
        if name.startswith("_"):
            raise AttributeError(name)
        return 0


class _RemoteRM:
    """The slice of the RequestManager surface the ClusterManager
    drives, proxied over the owner's transport (see module docstring
    for the mirror semantics)."""

    prefix_cache = None  # scoring goes through RemoteReplica.prefix_score

    def __init__(self, owner: "RemoteReplica"):
        self._owner = owner
        self.requests: Dict[int, _RequestView] = {}
        self.stats = _RemoteStats()
        self.hold_finished: set = set()

    def submit(
        self,
        prompt: Union[str, Sequence[int]],
        gen: Optional[GenerationConfig] = None,
        max_new_tokens: Optional[int] = None,
        trace_id: Optional[int] = None,
    ) -> int:
        if isinstance(prompt, str):
            raise ValueError(
                "remote replicas take token-list prompts (the cluster "
                "front-end tokenizes)"
            )
        gen = gen or GenerationConfig()
        if max_new_tokens is not None:
            gen = dataclasses.replace(gen, max_new_tokens=max_new_tokens)
        owner = self._owner
        args = {
            "tokens": [int(t) for t in prompt], "gen": gen_to_wire(gen),
        }
        if trace_id is not None:
            # cross-host correlation: the trace id rides the RPC
            # envelope so the server-side scheduler's spans for this
            # request stitch under the cluster-wide timeline
            args["trace_id"] = int(trace_id)
        res = owner._rpc("submit", args)
        rid = int(res["rid"])
        view = _RequestView(rid)
        self.requests[rid] = view
        owner._apply_envelope(res)
        view.profile.transport_retries += owner._last_call_retries
        return rid

    def hold_on_finish(self, rid: int) -> None:
        self._owner._rpc("hold_on_finish", {"rid": int(rid)})
        self.hold_finished.add(int(rid))

    def release_held(self, rid: int) -> None:
        res = self._owner._rpc("release_held", {"rid": int(rid)})
        self.hold_finished.discard(int(rid))
        self._owner._apply_envelope(res)

    def bind_profile(self, rid: int, profile: ProfileInfo) -> None:
        """Attach the carried cluster-side profile to a view (failover
        re-admission / migration adoption): later envelope merges add
        this home's counters on top of the carried totals."""
        self.requests[int(rid)].profile = profile

    def drain(self) -> None:
        self._owner.drain()

    def generate(self, prompts, gen=None, max_new_tokens=None):
        """Blocking convenience driver (bench warmup parity with the
        in-process ``rep.rm.generate``): submit, step to completion,
        return the mirrored outputs."""
        owner = self._owner
        rids = [self.submit(p, gen, max_new_tokens) for p in prompts]
        while any(
            self.requests[r].status not in TERMINAL_STATUSES for r in rids
        ):
            if not owner.step():
                break
        owner.drain()
        return [self.requests[r] for r in rids]


class RemoteReplica:
    """One cluster member living behind a transport (see module
    docstring). Carries the exact Replica telemetry/scheduling/fault
    surface the Router and ClusterManager drive."""

    is_remote = True

    def __init__(
        self,
        index: int,
        transport: Transport,
        serving,
        *,
        role: str = "mixed",
        stats=None,
        local=None,
    ):
        self.index = int(index)
        self.role = role
        self.transport = transport
        self.serving = serving
        self.rm = _RemoteRM(self)
        self.local = local  # loopback: the wrapped in-process Replica
        self.fault_injector = None
        self.steps_taken = 0
        self.injected_latency_s = 0.0
        #: cluster step of the last successful exchange — the manager
        #: stamps it; heartbeat-gap detection compares against it in
        #: CLUSTER steps (deterministic, no wall clock)
        self.last_contact_step = 0
        self._stats_src = stats
        # Seqs start at a random 62-bit point per CLIENT INCARNATION,
        # not at 1: a recovered manager re-dialing a STILL-RUNNING
        # server (ClusterManager.recover) must not collide with the
        # server's bounded response cache for the dead manager's seqs —
        # a collision replays the old client's cached response instead
        # of executing the new call. Retries still reuse one seq, so
        # the at-most-once contract is untouched; nothing downstream
        # depends on seq values (bitwise tests assert on outputs).
        self._seq = itertools.count(
            random.SystemRandom().getrandbits(62) | 1
        )
        self._telemetry: Dict[str, Any] = {}
        self._pending_abandon = False
        self._last_call_retries = 0
        self._log = get_logger("serve")
        # Observability: the WIRE tracer — rpc spans, retries and
        # envelope-shipped server events land on it when
        # obs.attach_observability wires a live one (lane "wire",
        # clocked by the client-side step counter).
        self.tracer = NULL_TRACER

    def bind_stats(self, stats) -> None:
        """Late-bind the ClusterStats source (the manager owns it but
        replicas are built first) — the transport's wire-byte counters
        follow the same callable."""
        self._stats_src = stats
        self.transport._stats_src = stats

    @property
    def stats(self):
        return (
            self._stats_src() if callable(self._stats_src)
            else self._stats_src
        )

    @property
    def engine(self):
        """The underlying engine when one is reachable in-process
        (loopback — lets the oom fault kind squeeze the real pool);
        None behind a socket."""
        return self.local.engine if self.local is not None else None

    # ------------------------------------------------------------------
    # the RPC core: deadline + bounded retries + exponential backoff

    def _rpc(self, method: str, args: Dict[str, Any],
             retryable: bool = True) -> Any:
        # issue-then-immediately-harvest: on an inline transport this
        # IS the pre-async serial exchange, bit for bit — one retry
        # state machine serves both drive loops (see _AsyncCall)
        return _AsyncCall(self, method, args, retryable=retryable).wait()

    def _apply_envelope(self, result: Dict[str, Any]) -> None:
        tel = result.get("telemetry")
        if tel is not None:
            self._telemetry = tel
            self.rm.stats.update(tel.get("stats") or {})
            self.rm.hold_finished = set(tel.get("hold_finished") or ())
            shipped = tel.get("trace_events")
            if shipped and self.tracer.enabled:
                # the replica server's spans come home inside every
                # state-bearing envelope — merge them (already tagged
                # with the replica lane) so the front-end's buffer
                # holds ONE stitched cross-host timeline
                self.tracer.buffer.extend(
                    shipped, lane=f"replica{self.index}"
                )
        for rid, state in (result.get("updates") or {}).items():
            view = self.rm.requests.get(int(rid))
            if view is not None:
                view.apply(state)

    def _spread_step_retries(self) -> None:
        """Mirror transport retries spent on this step/drain into every
        live request's profile (ISSUE: per-request
        ``ProfileInfo.transport_retries``) — the retried RPC carried
        all of their work."""
        if not self._last_call_retries:
            return
        for view in self.rm.requests.values():
            if view.status not in TERMINAL_STATUSES:
                view.profile.transport_retries += self._last_call_retries

    def _flush_pending_abandon(self) -> None:
        """An ``abandon`` that could not reach the server (the replica
        went DOWN because the link died) replays before the next
        exchange — a recovered replica must start from a clean
        scheduler, exactly like the in-process probe re-admission."""
        if not self._pending_abandon:
            return
        self._rpc("abandon", {})
        self._pending_abandon = False

    # ------------------------------------------------------------------
    # router-facing telemetry (mirror-computed — see module docstring)

    def prefix_score(self, tokens: Sequence[int]) -> int:
        if len(tokens) < 2:
            return 0
        try:
            return int(self._rpc("prefix_score",
                                 {"tokens": [int(t) for t in tokens]}
                                 )["score"])
        except (TransportError, RemoteError):
            # an unreachable replica scores 0 — routing falls elsewhere
            # and the health machinery catches the outage via its own
            # step/heartbeat observations
            return 0

    def active_requests(self) -> int:
        return sum(
            1 for v in self.rm.requests.values()
            if v.status not in TERMINAL_STATUSES
        )

    def load(self) -> float:
        return float(self.active_requests())

    def backlog_tokens(self) -> int:
        n = 0
        for v in self.rm.requests.values():
            if v.status in TERMINAL_STATUSES:
                continue
            if v.status is RequestStatus.DECODING:
                n += 1
            else:
                n += max(0, v.prompt_len - v.n_sched)
        return n

    def token_rate(self) -> float:
        return float(self._telemetry.get("token_rate", 0.0))

    def queue_delay_s(self) -> float:
        if (
            int(self._telemetry.get("rate_samples", 0)) < 2
            or self.token_rate() <= 0.0
        ):
            return 0.0
        return self.backlog_tokens() / self.token_rate()

    # ------------------------------------------------------------------
    # scheduling passthrough

    def has_work(self) -> bool:
        return self.active_requests() > 0 or bool(
            self._telemetry.get("has_work", False)
        )

    def heartbeat(self) -> bool:
        """One liveness + telemetry exchange. Returns False on failure
        — the manager's GAP accounting (cluster steps since last
        contact) turns sustained failures into health observations;
        single losses just cost a retry."""
        try:
            self._flush_pending_abandon()
            res = self._rpc("heartbeat", {})
        except (TransportError, RemoteError):
            return False
        self._apply_envelope(res)
        return True

    def step(self) -> bool:
        self.steps_taken += 1
        self.injected_latency_s = 0.0
        if self.fault_injector is not None:
            self.fault_injector.on_step(self)  # may raise InjectedFault
        self._flush_pending_abandon()
        res = self._rpc("step", {})
        self._apply_envelope(res)
        self._spread_step_retries()
        return bool(res.get("progressed", False))

    # ------------------------------------------------------------------
    # async issue/finish pairs — the concurrent drive loop's surface.
    # ISSUE methods run everything the serial path ran BEFORE its
    # blocking exchange (fault kinds, abandon replay, bookkeeping) and
    # may raise exactly what the serial path raised there; FINISH
    # methods harvest the response and apply the envelope→mirror
    # update. The manager issues in replica-index order, then finishes
    # in replica-index order — so every mirror/stats/tracer mutation
    # happens on the MANAGER's thread in a deterministic order no
    # matter how completions interleave on the wire.

    def step_async(self) -> "_AsyncCall":
        """Issue this replica's step RPC without waiting. Replica-kind
        faults fire here (issue time is the serial loop's call site) —
        may raise InjectedFault/TransportError exactly like
        :meth:`step`'s pre-exchange half."""
        self.steps_taken += 1
        self.injected_latency_s = 0.0
        if self.fault_injector is not None:
            self.fault_injector.on_step(self)  # may raise InjectedFault
        self._flush_pending_abandon()
        return _AsyncCall(self, "step", {})

    def finish_step(self, call: "_AsyncCall") -> bool:
        """Harvest a :meth:`step_async` ticket: envelope→mirror, retry
        spread, progressed flag. Raises the final TransportError on
        retry exhaustion — the manager feeds it to the health machine
        like a serial step failure."""
        res = call.wait()
        self._apply_envelope(res)
        self._spread_step_retries()
        return bool(res.get("progressed", False))

    def heartbeat_async(self) -> Optional["_AsyncCall"]:
        """Issue a liveness+telemetry exchange without waiting. Returns
        None when the pending-abandon replay (which must precede any
        exchange) could not be delivered — the heartbeat is already a
        failure."""
        try:
            self._flush_pending_abandon()
        except (TransportError, RemoteError):
            return None
        return _AsyncCall(self, "heartbeat", {})

    def finish_heartbeat(self, call: Optional["_AsyncCall"]) -> bool:
        if call is None:
            return False
        try:
            res = call.wait()
        except (TransportError, RemoteError):
            return False
        self._apply_envelope(res)
        return True

    def prefix_score_async(self,
                           tokens: Sequence[int]) -> Optional["_AsyncCall"]:
        """Issue a prefix-cache peek without waiting (None for prompts
        too short to score — the serial fast path)."""
        if len(tokens) < 2:
            return None
        return _AsyncCall(
            self, "prefix_score", {"tokens": [int(t) for t in tokens]}
        )

    def finish_prefix_score(self, call: Optional["_AsyncCall"]) -> int:
        if call is None:
            return 0
        try:
            return int(call.wait()["score"])
        except (TransportError, RemoteError):
            # an unreachable replica scores 0 — routing falls elsewhere
            # and the health machinery catches the outage via its own
            # step/heartbeat observations
            return 0

    def drain(self) -> None:
        self._flush_pending_abandon()
        res = self._rpc("drain", {})
        self._apply_envelope(res)
        self._spread_step_retries()

    # ------------------------------------------------------------------
    # fault tolerance

    def reset_rate(self) -> None:
        self._telemetry["token_rate"] = 0.0
        self._telemetry["rate_samples"] = 0

    def abandon(self) -> int:
        """Client-side teardown ALWAYS happens (the mirror is the
        manager's truth and must drop to zero load even when the
        transport is gone); the server-side teardown replays on the
        next successful exchange if it cannot be delivered now."""
        dropped = 0
        for view in self.rm.requests.values():
            view.pipeline_refs = 0
            if view.status not in TERMINAL_STATUSES:
                view.status = RequestStatus.ERROR
                view.error = "replica down — failed over"
                dropped += 1
        self.rm.hold_finished = set()
        self.reset_rate()
        self._telemetry["has_work"] = False
        try:
            self._rpc("abandon", {})
            self._pending_abandon = False
        except (TransportError, RemoteError) as exc:
            self._pending_abandon = True
            self._log.warning(
                "replica %d abandon could not be delivered (%s) — "
                "replaying before its next exchange", self.index, exc,
            )
        return dropped

    # ------------------------------------------------------------------
    # migration + standby adoption (page bytes over the wire)

    def migrate_out(self, rid: int) -> Dict[str, Any]:
        return self._rpc("migrate_out", {"rid": int(rid)})

    def migrate_in(self, payload: Dict[str, Any],
                   gen: GenerationConfig,
                   trace_id: Optional[int] = None) -> Optional[int]:
        args = {
            "tokens": payload["tokens"],
            "prompt_len": payload["prompt_len"],
            "prompt": payload.get("prompt", ""),
            "page_size": payload["page_size"],
            "pages": payload["pages"],
            "gen": gen_to_wire(gen),
        }
        if trace_id is not None:
            # the trace context follows the pages: the decode server's
            # adoption + decode spans stitch under the same timeline
            args["trace_id"] = int(trace_id)
        res = self._rpc("migrate_in", args)
        rid = res.get("rid")
        if rid is None:
            self._apply_envelope(res)
            return None
        rid = int(rid)
        self.rm.requests[rid] = _RequestView(rid)
        self._apply_envelope(res)
        return rid

    def export_prefix_tree(self) -> List[Dict[str, Any]]:
        return self._rpc("export_tree", {})["entries"]

    def import_prefix_tree(self, entries: List[Dict[str, Any]]) -> int:
        res = self._rpc("import_tree", {"entries": entries})
        self._apply_envelope(res)
        return int(res.get("adopted", 0))

    # ------------------------------------------------------------------
    # audits

    def check_no_leaks(self) -> None:
        """Run the page-pool refcount audit ON the replica; a remote
        ``AssertionError`` surfaces here as :class:`RemoteError` with
        the audit's message."""
        self._rpc("check_no_leaks", {})

    def close(self) -> None:
        self.transport.close()

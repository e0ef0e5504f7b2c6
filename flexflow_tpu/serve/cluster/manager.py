"""ClusterManager — one process driving N engine replicas.

The cluster front-end: RequestManager-shaped API (``submit`` /
``step`` / ``drain`` / ``generate`` / ``generate_stream`` / ``result``)
over a pool of :class:`Replica` (each its own engine, mesh and KV pool)
behind a :class:`Router`. The manager owns cluster-level request
identity (cluster ids are independent of any replica's guids), the
per-step drive loop over every replica's scheduler, and — under
disaggregation — the prefill→decode page migrations.

Request lifecycle::

    submit ──router──┬── shed / all DOWN ──→ ERROR (terminal, PR-2 contract)
                     ├── mixed replica ─────→ prefill+decode there ("single")
                     └── prefill replica ───→ prefill, max_new_tokens=1
                             │ held slot        ("prefill")
                             └─ COMPLETED → migration queue → decode replica
                                             adopts into DECODING ("decode")

Sheds come from SLO admission (``ServingConfig.slo_queue_delay_s``):
they surface as ``GenerationResult.error`` exactly like the PR-2
unservable-request path — a shed request is terminal the moment it is
submitted and can never hang a ``generate()``/stream/C-host loop.

**Fault tolerance** (serve/cluster/health.py): every replica step runs
under the health monitor — a step exception or sustained latency spike
demotes the replica (HEALTHY → SUSPECT → DOWN), and a DOWN replica's
circuit opens: it leaves ``Router.route`` scoring, its session
affinities drop (they re-pin on survivors, which also re-seeds its
prefix families there), and every request it held is RE-ADMITTED to a
healthy replica through the recompute path — prompt + tokens generated
so far resubmit as a prompt, exactly the vLLM-style preemption recompute
the scheduler already runs, so greedy generations stay bitwise the
fault-free run's. Retries are bounded (``ServingConfig.failover_retries``
with exponential cluster-step backoff); when they exhaust, or no healthy
replica remains, the request turns into a terminal
``GenerationResult.error`` — never a hang. After an exponential backoff
the breaker half-opens (PROBING) and routed traffic is the probe.

**Migration back-pressure** (``ServingConfig.migration_queue_budget``):
finished prefills waiting for decode-pool capacity sit in a bounded
FIFO. Within budget they wait holding their pages (the cheap page
hand-off); past it they release the pages immediately and drain through
recompute re-admission on the decode pool's own pending queue — a full
decode pool costs recompute, not unbounded held slots on the prefill
pool. Degraded pools fall back: a dead decode pool means the surviving
pool serves both phases (recompute re-admission in place of page
migration); a dead prefill pool routes new requests single-phase onto
the decode pool.

With ``replicas=1`` and no faults the manager routes everything to
replica 0 and the replica runs the bit-for-bit single-engine scheduler —
the router adds bookkeeping, never a different step sequence (asserted
bitwise in tests/test_cluster.py).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import (
    Any, Dict, Iterator, List, Optional, Sequence, Set, Tuple, Union,
)

from ...logging_utils import get_logger
from ...metrics import ClusterStats
from ...obs.tracer import NULL_TRACER
from ..batch_config import (
    GenerationConfig,
    GenerationResult,
    ProfileInfo,
    StreamEvent,
)
from ..engine import ServingConfig
from ..request_manager import TERMINAL_STATUSES, RequestStatus
from .health import HealthConfig, HealthMonitor, HealthState, ReplicaHealth
from .journal import RequestJournal, replay_journal
from .migration import migrate_request
from .reconfigure import (
    begin_scale_in as _begin_scale_in,
    maybe_retire as _maybe_retire,
    scale_in as _scale_in,
    scale_out as _scale_out,
    set_pools as _set_pools,
)
from .remote import HeartbeatGap, RemoteReplica
from .replica import Replica
from .router import Router
from .transport import LoopbackTransport, SocketTransport


def _wire_session(session_id: Optional[object]):
    """Session ids ride the journal as codec-safe primitives; anything
    richer journals as its string form (affinity pins do not survive a
    restart anyway — the journaled id only re-keys future turns)."""
    if session_id is None or isinstance(session_id, (int, str, float, bool)):
        return session_id
    return str(session_id)


_THIS_HOST = ("", "127.0.0.1", "localhost", "::1")


def _holds_tpu() -> bool:
    """True once this process has initialised a TPU backend (and so
    holds the chip) — asked without initialising one."""
    import jax
    from jax._src import xla_bridge  # no public "is a backend up?" query

    return (xla_bridge.backends_are_initialized()
            and jax.default_backend() == "tpu")


def _build_member(serving, ctx, index: int, role: str,
                  endpoint: Optional[str] = None):
    """One replica (or standby) behind the configured transport —
    shared by :meth:`ClusterManager.build`, :meth:`ClusterManager.
    recover` and ``reconfigure.scale_out``. "loopback" wraps the SAME
    in-process build in a RemoteReplica whose every call round-trips
    the wire codec against a ReplicaServerCore; "socket" dials a
    subprocess replica server (``endpoint``, falling back to the
    config's positional entry) instead of building anything locally."""
    if serving.replica_transport == "socket":
        ep = endpoint
        if ep is None:
            if index >= len(serving.replica_endpoints):
                raise ValueError(
                    f"no endpoint for socket replica {index} — pass "
                    "scale_out(endpoint=...) or extend replica_endpoints"
                )
            ep = serving.replica_endpoints[index]
        host, _, port = ep.rpartition(":")
        if host in _THIS_HOST and _holds_tpu():
            raise RuntimeError(
                f"socket replica {index} at {ep} is a process on this "
                "host, and this process has already initialised the TPU "
                "backend: a chip belongs to one process at a time, so "
                "that replica server can never get one (it would fail "
                "or hang at start-up). Drive local replicas in-process "
                "(replica_transport='inproc' or 'loopback', one device "
                "each), or start the manager in a process that does "
                "not touch the TPU."
            )
        return RemoteReplica(
            index, SocketTransport(host or "127.0.0.1", int(port)),
            serving, role=role,
        )
    devs = ctx["devices"]
    local = Replica.build(
        index, ctx["model"], ctx["cfg"], ctx["params"], serving,
        role=role,
        devices=[devs[index % len(devs)]],
        tokenizer=ctx["tokenizer"],
        eos_token_id=ctx["eos_token_id"],
        seed=ctx["seed"],
        ssms=ctx["ssms"],
        spec=ctx["spec"],
    )
    if serving.replica_transport == "inproc":
        return local
    from .server import ReplicaServerCore

    return RemoteReplica(
        index, LoopbackTransport(ReplicaServerCore(local).dispatch),
        serving, role=role, local=local,
    )


@dataclasses.dataclass
class ClusterRequest:
    """Cluster-level view of one request: where it lives now (replica
    position + replica-local rid) and which phase of the disaggregated
    lifecycle it is in. ``rid is None`` means the request is not on any
    replica right now: shed / terminally failed (``error`` set) or
    between homes awaiting a failover re-admission (``error`` None)."""

    cluster_id: int
    tokens: List[int]
    prompt_text: str
    gen: GenerationConfig
    session_id: Optional[object] = None
    replica: Optional[int] = None       # position into manager.replicas
    rid: Optional[int] = None           # replica-local request id
    phase: str = "single"               # "single" | "prefill" | "decode"
    error: Optional[str] = None         # terminal failure (shed/failover)
    # terminal-success WITHOUT a live home: set when the request's home
    # retired (scale_in) or when a recovered manager rehydrated its
    # journaled terminal record — ``_known`` holds the full transcript
    finished: bool = False
    profile: ProfileInfo = dataclasses.field(default_factory=ProfileInfo)
    # ORIGINAL prompt length (the output-token baseline): a failover
    # re-admission's home sees prompt+generated as its prompt, so the
    # home's prompt_len stops being the boundary — this one always is.
    prompt_len: int = 0
    retries: int = 0                    # re-admissions so far
    mig_attempts: int = 0               # failed page-migration attempts

    _manager: Any = dataclasses.field(default=None, repr=False)
    # prompt + flushed generated tokens captured when the home replica
    # went DOWN — the recompute re-admission's submission (and the
    # partial output while between homes)
    _known: Optional[List[int]] = dataclasses.field(default=None, repr=False)
    _retry_at_step: int = 0             # failover/migration backoff gate

    @property
    def status(self) -> RequestStatus:
        """RequestStatus-shaped view (c_backend drives clusters through
        the same loop it drives a bare RequestManager with)."""
        if self.rid is None:
            # shed / failed = terminal; retired-home / recovered
            # completions = COMPLETED; between homes (failover pending)
            # = PENDING, so nothing treats an in-flight recovery as done
            if self.error:
                return RequestStatus.ERROR
            if self.finished:
                return RequestStatus.COMPLETED
            return RequestStatus.PENDING
        home = self._manager.replicas[self.replica].rm
        st = home.requests[self.rid].status
        if self.phase == "prefill" and st in TERMINAL_STATUSES:
            # completed ON THE PREFILL POOL means "awaiting migration",
            # not done — unless the manager decided it finished there
            return (
                st if st is RequestStatus.ERROR
                else RequestStatus.DECODING
            )
        return st

    @property
    def output_tokens(self) -> List[int]:
        if self.rid is None:
            if self._known:
                return list(self._known[self.prompt_len:])
            return []
        home = self._manager.replicas[self.replica].rm
        # slice at the ORIGINAL prompt boundary: a failover home's own
        # prompt_len includes carried-over generated tokens
        return home.requests[self.rid].tokens[self.prompt_len:]


class ClusterManager:
    """Drive ``replicas`` behind a router (see module docstring)."""

    def __init__(
        self,
        replicas: Sequence[Replica],
        serving: ServingConfig,
        *,
        router: Optional[Router] = None,
        tokenizer: Any = None,
        eos_token_id: Optional[int] = None,
        health_config: Optional[HealthConfig] = None,
        standbys: Sequence[Replica] = (),
    ):
        serving.validate_cluster()
        if len(replicas) != serving.replicas:
            raise ValueError(
                f"ServingConfig.replicas={serving.replicas} but "
                f"{len(replicas)} replicas were built"
            )
        if len(standbys) != serving.standby_replicas:
            raise ValueError(
                f"ServingConfig.standby_replicas="
                f"{serving.standby_replicas} but {len(standbys)} "
                "standbys were built"
            )
        self.serving = serving
        self.replicas = list(replicas)
        # warm standbys: pre-built engines OUTSIDE routing; on a DOWN
        # transition one adopts the dead replica's position (+ its
        # prefix families over the transport) — see _adopt_standby
        self.standbys = list(standbys)
        self._retired: List[Replica] = []   # replaced dead replicas
        self.tokenizer = tokenizer
        self.eos_token_id = eos_token_id
        if eos_token_id is None and tokenizer is not None:
            self.eos_token_id = getattr(tokenizer, "eos_token_id", None)
        self.stats = ClusterStats()
        for rep in list(self.replicas) + self.standbys:
            if getattr(rep, "is_remote", False):
                rep.bind_stats(lambda: self.stats)
        self.health = HealthMonitor(len(self.replicas), health_config)
        self.fault_injector = None
        # replica positions already observed failing THIS cluster step
        # (the one-SUSPECT-observation-per-step guard: a replica that is
        # simultaneously in a heartbeat gap and returning RPC errors is
        # observed once, preserving the PR-9 threshold arithmetic)
        self._failed_obs: Set[int] = set()
        self.prefill_pool = [r for r in self.replicas if r.role == "prefill"]
        self.decode_pool = [r for r in self.replicas if r.role == "decode"]
        self.disaggregated = bool(self.prefill_pool)
        if self.disaggregated and not self.decode_pool:
            raise ValueError("prefill pool without a decode pool")
        # Live reconfiguration (serve/cluster/reconfigure.py): replica
        # INDICES currently draining toward retirement — excluded from
        # every placement exactly like DOWN replicas, but still stepped
        # (their in-flight work finishes or migrates; maybe_retire
        # removes them once idle). Keyed by index, not position, so
        # membership surgery never invalidates the set.
        self._draining: Set[int] = set()
        routing = self.prefill_pool if self.disaggregated else self.replicas
        # router positions index the ROUTING pool; map back to cluster
        # positions so ClusterRequest.replica is always cluster-wide
        self._routing_pos = [self.replicas.index(r) for r in routing]
        health_cb = (
            lambda pos: self._routable_pos(self._routing_pos[pos])
        )
        self.router = router or Router(
            routing,
            serving.router_policy,
            slo_queue_delay_s=serving.slo_queue_delay_s,
            stats=lambda: self.stats,
            health=health_cb,
        )
        if router is not None and self.router.health is None:
            self.router.health = health_cb
        self.requests: Dict[int, ClusterRequest] = {}
        self._next_cid = 1
        self._step_counter = 0
        # failover re-admissions pending their backoff (cluster ids)
        self._failovers: List[int] = []
        # finished prefills awaiting decode-pool capacity (cluster ids,
        # FIFO; bounded by ServingConfig.migration_queue_budget)
        self._migration_queue: List[int] = []
        self._mig_queued: Set[int] = set()
        self._log = get_logger("serve")
        # Observability (flexflow_tpu/obs): the router/manager lane of
        # the cluster timeline (placements, migrations, failovers,
        # health transitions, heartbeat gaps) plus the failure flight
        # recorder's dump triggers. NULL_TRACER/None by default — the
        # drive loop pays one attribute read per guarded site;
        # obs.attach_observability wires live ones in.
        self.tracer = NULL_TRACER
        self.flight_recorder = None
        # events recorded before a tracer could attach (recovery runs
        # before obs wiring) — flushed on the first traced step
        self._pending_trace: List[tuple] = []
        # Elastic control plane (journal.py + reconfigure.py): the
        # durable request journal (opened by build/recover — see
        # _open_journal), per-request flushed-token high-water marks,
        # terminal records already written, the replica factory context
        # scale_out/recover rebuild members from, and the index→endpoint
        # map the members snapshot journals for socket clusters.
        self.journal: Optional[RequestJournal] = None
        self._journal_flushed: Dict[int, int] = {}
        self._journal_done: Set[int] = set()
        self._build_ctx: Optional[Dict[str, Any]] = None
        self._endpoints: Dict[int, str] = {}
        self._next_replica_index = 1 + max(
            (r.index for r in list(self.replicas) + self.standbys),
            default=-1,
        )
        # Self-driving serving (serve/autotune): the optional policy
        # loop hooked into step() — attached by build()/recover() when
        # ServingConfig.autoscale is set, or injected by tests. The
        # completion window feeds its TrafficEstimator: cluster ids
        # still awaiting their terminal sweep, plus this-window
        # (prompt_len, output_len) pairs for newly finished requests,
        # drained by drain_completion_window() once per observation.
        self.autoscaler = None
        self._open_cids: Set[int] = set()
        self._completion_window: List[Tuple[int, int]] = []

    # ------------------------------------------------------------------
    # construction

    @classmethod
    def build(
        cls,
        model: Any,
        cfg: Any,
        params: Any,
        serving: Optional[ServingConfig] = None,
        *,
        tokenizer: Any = None,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        devices: Optional[Sequence[Any]] = None,
        health_config: Optional[HealthConfig] = None,
        ssms: Sequence[Any] = (),
        spec: Any = None,
    ) -> "ClusterManager":
        """Build ``serving.replicas`` in-process replicas — params
        shared by reference, each replica with its own mesh over a
        device picked round-robin from ``devices`` (all of them on a
        1-device host: independent engines on one chip is the
        in-process cluster this PR ships; per-host processes slot in
        behind the same Replica surface later).

        ``ssms`` ((model, cfg, params) triples) + ``spec`` turn every
        replica into a SpecInfer pair: per-replica SSM MIRRORS — each
        replica builds its own draft engines on its own mesh (draft
        params shared by reference, like the target's), so speculation
        scales out with the pool. Disaggregated prefill/decode pools
        reject the combination at ``validate_cluster``."""
        serving = serving or ServingConfig()
        serving.validate_cluster(
            specinfer=bool(ssms)
            or getattr(spec, "draft", "ssm") == "early_exit"
        )
        import jax

        devs = list(devices or jax.devices())
        roles = ["mixed"] * serving.replicas
        if serving.prefill_replicas:
            roles = (
                ["prefill"] * serving.prefill_replicas
                + ["decode"] * serving.decode_replicas
            )
        ctx = dict(
            model=model, cfg=cfg, params=params, devices=devs,
            tokenizer=tokenizer, eos_token_id=eos_token_id, seed=seed,
            ssms=ssms, spec=spec,
        )
        replicas = [
            _build_member(serving, ctx, i, roles[i])
            for i in range(serving.replicas)
        ]
        standbys = [
            _build_member(serving, ctx, serving.replicas + j, "mixed")
            for j in range(serving.standby_replicas)
        ]
        cm = cls(
            replicas, serving, tokenizer=tokenizer,
            eos_token_id=eos_token_id, health_config=health_config,
            standbys=standbys,
        )
        cm._build_ctx = ctx
        if serving.replica_transport == "socket":
            cm._endpoints = {
                i: serving.replica_endpoints[i]
                for i in range(serving.replicas)
            }
        # build() starts a FRESH log (use recover() to resume one): a
        # stale journal replaying into a new cluster would resurrect a
        # previous run's requests
        cm._open_journal(resume=False)
        if serving.autoscale:
            cm._attach_autoscaler()
        return cm

    @classmethod
    def recover(
        cls,
        model: Any,
        cfg: Any,
        params: Any,
        serving: Optional[ServingConfig] = None,
        *,
        tokenizer: Any = None,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        devices: Optional[Sequence[Any]] = None,
        health_config: Optional[HealthConfig] = None,
        ssms: Sequence[Any] = (),
        spec: Any = None,
    ) -> "ClusterManager":
        """Rebuild a crashed manager from ``serving.journal_dir``.

        The journal replays first (a torn tail truncates — never
        corrupts), yielding the last COMMITTED membership (scale_out /
        scale_in / set_pools survive the crash; an uncommitted begin
        recovers as "never happened") and every journaled request with
        its flushed-token prefix. Replicas rebuild per that membership:
        still-running subprocess servers are RECONNECTED — a heartbeat
        rebuilds the client mirror from its envelope, then ``abandon``
        clears the orphaned scheduler state (the PR-12 seq cache keeps
        the replayed RPCs at-most-once; the server's prefix tree
        survives, so it rejoins WARM) — while in-process/loopback
        replicas, which died with the manager, rebuild fresh. Every
        unfinished request then re-admits through the PR-9 recompute
        path with its journaled prompt + flushed prefix, so greedy
        outputs are BITWISE the uninterrupted run's and already-
        delivered tokens are regenerated identically, never duplicated
        (stream-monotone across the restart). Terminal entries
        rehydrate so ``result`` still answers for them."""
        serving = serving or ServingConfig()
        if not serving.journal_dir:
            raise ValueError(
                "ClusterManager.recover needs ServingConfig.journal_dir "
                "(there is no journal to recover from)"
            )
        serving.validate_cluster(
            specinfer=bool(ssms)
            or getattr(spec, "draft", "ssm") == "early_exit"
        )
        state = replay_journal(cls._journal_path(serving))
        import jax

        devs = list(devices or jax.devices())
        roles = ["mixed"] * serving.replicas
        if serving.prefill_replicas:
            roles = (
                ["prefill"] * serving.prefill_replicas
                + ["decode"] * serving.decode_replicas
            )
        is_socket = serving.replica_transport == "socket"
        members = state.members or [
            {"index": i, "role": roles[i],
             "endpoint": (serving.replica_endpoints[i] if is_socket
                          else "")}
            for i in range(serving.replicas)
        ]
        # standby endpoints stay config-positional (the tail entries);
        # the MEMBER endpoints come from the journaled snapshot, which
        # survives scale_out/scale_in having changed them
        standby_eps = (
            serving.replica_endpoints[len(serving.replica_endpoints)
                                      - serving.standby_replicas:]
            if is_socket and serving.standby_replicas else ()
        )
        n_prefill = sum(1 for m in members if m["role"] == "prefill")
        n_decode = sum(1 for m in members if m["role"] == "decode")
        serving = dataclasses.replace(
            serving,
            replicas=len(members),
            prefill_replicas=n_prefill,
            decode_replicas=n_decode,
            replica_endpoints=(
                tuple(str(m.get("endpoint", "")) for m in members)
                + tuple(standby_eps)
            ) if is_socket else serving.replica_endpoints,
        )
        ctx = dict(
            model=model, cfg=cfg, params=params, devices=devs,
            tokenizer=tokenizer, eos_token_id=eos_token_id, seed=seed,
            ssms=ssms, spec=spec,
        )
        replicas = [
            _build_member(serving, ctx, int(m["index"]), str(m["role"]),
                          str(m.get("endpoint") or "") or None)
            for m in members
        ]
        max_idx = max((int(m["index"]) for m in members), default=-1)
        standbys = [
            _build_member(serving, ctx, max_idx + 1 + j, "mixed",
                          standby_eps[j] if standby_eps else None)
            for j in range(serving.standby_replicas)
        ]
        cm = cls(
            replicas, serving, tokenizer=tokenizer,
            eos_token_id=eos_token_id, health_config=health_config,
            standbys=standbys,
        )
        cm._build_ctx = ctx
        cm._endpoints = {
            int(m["index"]): str(m.get("endpoint", ""))
            for m in members if m.get("endpoint")
        }
        cm._next_replica_index = max_idx + 1 + serving.standby_replicas
        # reconnect still-running subprocess servers (see docstring);
        # loopback/inproc replicas were just rebuilt and need neither
        for rep in cm.replicas:
            if getattr(rep, "is_remote", False) and rep.local is None:
                if rep.heartbeat():
                    rep.abandon()
        # rehydrate the journaled requests
        cm._next_cid = state.next_cid
        replayed = 0
        now = time.perf_counter()
        for e in state.entries.values():
            cr = ClusterRequest(
                cluster_id=e.cid, tokens=list(e.tokens),
                prompt_text=e.prompt_text, gen=e.gen,
                session_id=e.session, prompt_len=e.prompt_len,
                _manager=cm,
            )
            cr._known = list(e.tokens) + list(e.flushed)
            cm.requests[e.cid] = cr
            cm._journal_flushed[e.cid] = len(e.flushed)
            if e.terminal:
                cr.error = e.error
                cr.finished = e.error is None
                cm._journal_done.add(e.cid)
            else:
                # recompute re-admission with the journaled prompt +
                # flushed prefix: retries=1 marks it a re-admission, so
                # _place keeps the ORIGINAL prompt_len boundary and the
                # carried profile (fresh clock — recovery restarts it)
                cr.profile.start_time = now
                cr.retries = 1
                cm._failovers.append(e.cid)
                replayed += 1
        cm.stats.submitted += len(state.entries)
        cm.stats.manager_recoveries += 1
        cm.stats.journal_replayed += replayed
        cm._pending_trace.append(("recover", dict(
            replicas=len(members), replayed=replayed,
            records=state.records,
        )))
        cm._pending_trace.append(("replay", dict(
            requests=len(state.entries), records=state.records,
            truncated_bytes=state.truncated_bytes,
        )))
        # resume the SAME log, compacted to the recovered state (the
        # full history was just replayed — rewriting it keeps replay
        # idempotent and the file bounded)
        cm._open_journal(resume=True)
        cm._journal_checkpoint(include_finished=True)
        # unfinished rehydrated requests re-enter the completion sweep;
        # a fresh autoscaler (cooldown re-armed from the current step)
        # resumes the policy loop over the recovered membership
        cm._open_cids = {
            cid for cid, cr in cm.requests.items()
            if cr.status not in TERMINAL_STATUSES
        }
        if serving.autoscale:
            cm._attach_autoscaler()
        cm._log.warning(
            "manager recovered from %s: %d replicas, %d requests "
            "rehydrated (%d re-admitted, %d already terminal)%s",
            cls._journal_path(serving), len(members), len(state.entries),
            replayed, len(state.entries) - replayed,
            f", {state.truncated_bytes}B torn tail truncated"
            if state.truncated_bytes else "",
        )
        return cm

    # ------------------------------------------------------------------
    # durable request journal (serve/cluster/journal.py)

    @staticmethod
    def _journal_path(serving: ServingConfig) -> str:
        return os.path.join(serving.journal_dir, "requests.journal")

    def _open_journal(self, resume: bool) -> None:
        if not self.serving.journal_dir:
            return
        path = self._journal_path(self.serving)
        if not resume and os.path.exists(path):
            self._log.warning(
                "journal %s exists — build() starts a FRESH log over "
                "it (use ClusterManager.recover to resume a crashed "
                "manager's journal)", path,
            )
            os.remove(path)
        self.journal = RequestJournal(path, stats=lambda: self.stats)

    def _journal_sync(self) -> None:
        """Batch-write flushed-token deltas + newly terminal records —
        called at the drive loop's flush sync points (end of step/
        drain/submit): one buffered write + one file flush, never a
        per-token write and never a device sync."""
        j = self.journal
        if j is None:
            return
        for cid, cr in self.requests.items():
            if cid in self._journal_done:
                continue
            out = cr.output_tokens
            sent = self._journal_flushed.get(cid, 0)
            if len(out) > sent:
                j.append({
                    "type": "tokens", "cid": cid,
                    "toks": [int(t) for t in out[sent:]],
                })
                self._journal_flushed[cid] = len(out)
            if cr.status in TERMINAL_STATUSES:
                err = cr.error
                if err is None and cr.rid is not None:
                    err = self.replicas[cr.replica].rm.requests[
                        cr.rid].error
                j.append({"type": "terminal", "cid": cid, "error": err})
                self._journal_done.add(cid)
                j.note_finished()
        j.flush()
        if j.should_compact():
            self._journal_checkpoint(include_finished=False)

    def _journal_checkpoint(self, include_finished: bool) -> None:
        """Rewrite the journal to the current live state (compaction —
        finished entries retire unless ``include_finished``, which the
        recovery checkpoint uses so results survive one more restart)."""
        j = self.journal
        if j is None:
            return
        from .server import gen_to_wire

        recs: List[Dict[str, Any]] = [
            {"type": "members", "members": self.members_snapshot()}
        ]
        for cid in sorted(self.requests):
            cr = self.requests[cid]
            done = cid in self._journal_done
            if done and not include_finished:
                continue
            out = cr.output_tokens
            recs.append({
                "type": "submit", "cid": cid,
                "tokens": [int(t) for t in cr.tokens[:cr.prompt_len]],
                "prompt_len": int(cr.prompt_len),
                "gen": gen_to_wire(cr.gen),
                "session": _wire_session(cr.session_id),
                "prompt": cr.prompt_text,
            })
            if out:
                recs.append({
                    "type": "tokens", "cid": cid,
                    "toks": [int(t) for t in out],
                })
                self._journal_flushed[cid] = len(out)
            if done:
                err = cr.error
                recs.append({"type": "terminal", "cid": cid, "error": err})
        j.compact(recs)

    def _make_member(self, index: int, role: str,
                     endpoint: Optional[str] = None):
        """Build (or dial) one more replica through the same factory
        construction used — scale_out's replica source."""
        if self._build_ctx is None:
            raise RuntimeError(
                "this cluster was constructed from prebuilt replicas "
                "(no build context) — pass scale_out(replica=...) a "
                "prebuilt one"
            )
        return _build_member(self.serving, self._build_ctx, index, role,
                             endpoint)

    def members_snapshot(self) -> List[Dict[str, Any]]:
        """The journaled membership: index/role/endpoint per replica —
        what :meth:`recover` rebuilds after reconfigurations moved the
        cluster away from the config's static shape."""
        return [
            {"index": r.index, "role": r.role,
             "endpoint": self._endpoints.get(r.index, "")}
            for r in self.replicas
        ]

    def close(self) -> None:
        """Flush + close the journal and every remote transport (the
        orderly shutdown; crash recovery never needs it)."""
        if self.journal is not None:
            self._journal_sync()
            self.journal.close()
        for rep in list(self.replicas) + self.standbys + self._retired:
            close_fn = getattr(rep, "close", None)
            if close_fn is not None:
                close_fn()

    # ------------------------------------------------------------------
    # live reconfiguration (serve/cluster/reconfigure.py)

    def scale_out(self, **kw) -> int:
        """Grow the cluster by one replica (warm by default) — see
        :func:`~.reconfigure.scale_out`."""
        return _scale_out(self, **kw)

    def begin_scale_in(self, pos: int) -> None:
        """Start draining the replica at ``pos`` (non-blocking) — see
        :func:`~.reconfigure.begin_scale_in`."""
        _begin_scale_in(self, pos)

    def scale_in(self, pos: int, **kw) -> None:
        """Drain + retire the replica at ``pos`` (blocking, bounded) —
        see :func:`~.reconfigure.scale_in`."""
        _scale_in(self, pos, **kw)

    def set_pools(self, roles: Dict[int, str]) -> None:
        """Flip replicas between prefill/decode pools under traffic —
        see :func:`~.reconfigure.set_pools`."""
        _set_pools(self, roles)

    def attach_faults(self, plan):
        """Wire a :class:`~.faults.FaultPlan` (or a prebuilt injector,
        or its JSON) into every replica (standbys included) and the
        migration path. Transport fault kinds (drop/delay/disconnect/
        partition) are injected AT the RPC transport, which in-process
        replicas do not have — aiming them at an ``inproc`` cluster is
        a loud error, not a silent no-op. Returns the
        :class:`~.faults.FaultInjector` for ``fired``/``release_all``."""
        from .faults import TRANSPORT_KINDS, FaultInjector, FaultPlan

        if isinstance(plan, str):
            plan = FaultPlan.from_json(plan)
        injector = plan if isinstance(plan, FaultInjector) else (
            FaultInjector(plan)
        )
        if any(f.kind == "sigkill" for f in injector.plan) and (
            self.serving.replica_transport != "socket"
        ):
            raise ValueError(
                "the 'sigkill' fault kind kills a real subprocess "
                "replica server — it needs replica_transport='socket' "
                "(and FaultInjector.register_process per target); use "
                "'crash' to script surface-level death elsewhere"
            )
        transport_faults = [
            f.kind for f in injector.plan if f.kind in TRANSPORT_KINDS
        ]
        if transport_faults and self.serving.replica_transport == "inproc":
            raise ValueError(
                f"fault plan contains transport kinds {transport_faults} "
                "but this cluster drives IN-PROCESS replicas "
                "(replica_transport='inproc') — transport faults are "
                "injected at the RPC layer; run with "
                "replica_transport='loopback' (or 'socket') to exercise "
                "them"
            )
        if self.serving.replica_transport == "socket" and any(
            f.kind == "oom" for f in injector.plan
        ):
            raise ValueError(
                "the 'oom' fault kind squeezes the replica's page pool "
                "in-process, which a socket-backed replica does not "
                "expose — use loopback replicas for oom scenarios"
            )
        self.fault_injector = injector
        for rep in list(self.replicas) + self.standbys:
            rep.fault_injector = injector
        return injector

    # ------------------------------------------------------------------
    # submission + placement

    def _tokenize(self, prompt: Union[str, Sequence[int]]):
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError("string prompt requires a tokenizer")
            return list(self.tokenizer.encode(prompt)), prompt
        return [int(t) for t in prompt], ""

    def _routable_pos(self, pos: int) -> bool:
        """May the router/failover/migration paths place work at this
        cluster position? DOWN (circuit open) and DRAINING (scale_in in
        progress) are both excluded — the one router-exclusion flow."""
        return (
            self.health[pos].routable
            and self.replicas[pos].index not in self._draining
        )

    def _routable_rep(self, rep: Replica) -> bool:
        return self._routable_pos(self.replicas.index(rep))

    def _drop_sessions(self, pos: int) -> int:
        """Re-home the sessions pinned to the replica at ``pos`` —
        the ONE flow both the DOWN path and the drain path use: each
        session re-pins on its next turn (which also re-seeds, or
        re-homes, the replica's prefix families on survivors)."""
        rep = self.replicas[pos]
        try:
            rpos = self.router.replicas.index(rep)
        except ValueError:
            return 0  # not in the routing pool (e.g. a decode replica)
        dropped = self.router.drop_replica_sessions(rpos)
        if dropped:
            self._log.debug(
                "replica %d: %d session affinities dropped (re-pin on "
                "survivors)", rep.index, dropped,
            )
        return dropped

    def submit(
        self,
        prompt: Union[str, Sequence[int]],
        gen: Optional[GenerationConfig] = None,
        max_new_tokens: Optional[int] = None,
        session_id: Optional[object] = None,
    ) -> int:
        """Route + queue one request; returns its CLUSTER id
        immediately (non-blocking — drive with :meth:`step` or a
        concurrent :meth:`generate`/:meth:`generate_stream`). A shed
        (or no-healthy-replica) request is terminal on return
        (``result`` carries the error)."""
        gen = gen or GenerationConfig()
        if max_new_tokens is not None:
            gen = dataclasses.replace(gen, max_new_tokens=max_new_tokens)
        tokens, text = self._tokenize(prompt)
        cid = self._next_cid
        self._next_cid += 1
        self.stats.submitted += 1
        cr = ClusterRequest(
            cluster_id=cid, tokens=tokens, prompt_text=text, gen=gen,
            session_id=session_id, prompt_len=len(tokens), _manager=self,
        )
        self.requests[cid] = cr
        self._open_cids.add(cid)
        self._place(cr, tokens)
        if self.journal is not None:
            # durable the moment submit returns: the journaled prompt
            # (post-placement — prompt_len is the home's authoritative,
            # possibly truncated, boundary) + GenerationConfig is what a
            # recovered manager re-promises. One record + one flush per
            # SUBMISSION, not per step — then the terminal sweep covers
            # the shed-on-arrival case.
            from .server import gen_to_wire

            self.journal.append({
                "type": "submit", "cid": cid,
                "tokens": [int(t) for t in cr.tokens[:cr.prompt_len]],
                "prompt_len": int(cr.prompt_len),
                "gen": gen_to_wire(gen),
                "session": _wire_session(session_id),
                "prompt": text,
            })
            self._journal_sync()
        return cid

    def _place_failed(self, cr: ClusterRequest, how: str) -> bool:
        cr.rid = None
        cr.replica = None
        if how == "shed":
            cr.error = (
                "shed by SLO admission: every replica's queue-delay "
                f"estimate exceeds slo_queue_delay_s="
                f"{self.serving.slo_queue_delay_s}"
            )
        else:  # "down"
            cr.error = (
                "no healthy replica: every replica is circuit-broken "
                "(DOWN) — the request fails terminally instead of "
                "waiting for a probe that may never succeed"
            )
        tr = self.tracer
        if tr.enabled:
            tr.event("place_failed", trace_id=cr.cluster_id, how=how)
        if self.flight_recorder is not None:
            self.flight_recorder.dump(
                self.tracer.lane or "router", "request_error",
                step=self._step_counter,
                extra={"cluster_id": cr.cluster_id, "how": how},
            )
        return False

    def _place(
        self,
        cr: ClusterRequest,
        known: Sequence[int],
        *,
        ignore_slo: bool = False,
    ) -> bool:
        """Route ``known`` (the prompt, or prompt + tokens generated so
        far on a failover re-admission) and submit it to the chosen
        replica. Returns True when placed; False means TERMINAL — shed,
        or no healthy replica (``cr.error`` set). Failover
        re-admissions pass ``ignore_slo=True``: a request admitted once
        is never shed on its second landing."""
        produced = max(0, len(known) - cr.prompt_len)
        remaining = cr.gen.max_new_tokens - produced
        gen_home = (
            cr.gen if produced == 0
            else dataclasses.replace(cr.gen, max_new_tokens=remaining)
        )
        first = cr.retries == 0
        phase = "single"
        if self.disaggregated and any(
            self._routable_rep(r) for r in self.prefill_pool
        ):
            pos, how = self.router.route(
                known, cr.session_id, ignore_slo=ignore_slo
            )
            if pos is None:
                return self._place_failed(cr, how)
            rep = self.replicas[self._routing_pos[pos]]
            if any(self._routable_rep(r) for r in self.decode_pool):
                phase = "prefill"
            else:
                # decode pool entirely DOWN: non-disaggregated serving
                # on the surviving prefill pool — the chosen replica
                # runs BOTH phases (no hold, no doomed migration)
                self._log.warning(
                    "decode pool is DOWN — request %d served "
                    "single-phase on prefill replica %d",
                    cr.cluster_id, rep.index,
                )
        elif self.disaggregated:
            # prefill pool entirely DOWN: fall back to non-disaggregated
            # serving on the surviving decode pool (ROADMAP'd degrade —
            # the decode replicas prefill too rather than refuse traffic)
            cands = [r for r in self.decode_pool if self._routable_rep(r)]
            if not cands:
                return self._place_failed(cr, "down")
            rep = min(
                cands,
                key=lambda r: (r.queue_delay_s(), r.load(), r.index),
            )
            self.stats.record_placement("pool_fallback")
            self._log.warning(
                "prefill pool is DOWN — request %d served single-phase "
                "on decode replica %d", cr.cluster_id, rep.index,
            )
        else:
            pos, how = self.router.route(
                known, cr.session_id, ignore_slo=ignore_slo
            )
            if pos is None:
                return self._place_failed(cr, how)
            rep = self.replicas[self._routing_pos[pos]]
        delay = rep.queue_delay_s()
        if first:
            # per-replica arrival accounting + the admission-time
            # queue-delay sample (what the router saw, not a later
            # re-read) — the autotune TrafficEstimator's raw inputs
            self.stats.note_arrival(rep.index)
            self.stats.note_queue_delay_s(delay)
        cr.replica = self.replicas.index(rep)
        cr.phase = phase
        if phase == "prefill":
            # prefill pass only: max_new_tokens=1 makes the prefill-final
            # dispatch (which samples the first output token on device)
            # the request's LAST step there — the chunked-prefill
            # boundary — and the held slot keeps its pages alive for
            # the migration that follows
            cr.rid = rep.rm.submit(
                known, dataclasses.replace(gen_home, max_new_tokens=1),
                trace_id=cr.cluster_id,
            )
            rep.rm.hold_on_finish(cr.rid)
        else:
            cr.rid = rep.rm.submit(known, gen_home,
                                   trace_id=cr.cluster_id)
        req = rep.rm.requests[cr.rid]
        if first:
            req.profile.replica_id = rep.index
            req.profile.router_queue_delay_s = delay
            cr.profile = req.profile
            # the home may have truncated an over-long prompt — its
            # prompt_len is the authoritative output boundary
            cr.prompt_len = req.prompt_len
        else:
            # re-admission: keep the ORIGINAL profile (start time, TTFT)
            # on the new home and record the move on it
            req.profile = cr.profile
            cr.profile.retries = cr.retries
            cr.profile.failover_replica_id = rep.index
            cr.profile.replica_id = rep.index
            cr.profile.router_queue_delay_s = delay
        cr._known = None
        tr = self.tracer
        if tr.enabled:
            tr.event(
                "place", trace_id=cr.cluster_id, replica=rep.index,
                phase=phase, retries=cr.retries,
            )
        return True

    # convenience alias (c_backend drives both manager kinds identically)
    def register_request(
        self,
        prompt: Union[str, Sequence[int]],
        gen: Optional[GenerationConfig] = None,
    ) -> int:
        return self.submit(prompt, gen)

    # ------------------------------------------------------------------
    # fault handling: health transitions + failover re-admission

    def _note_transition(self, pos: int, transition: Optional[str],
                         exc: Optional[BaseException] = None) -> None:
        if transition is None:
            return
        rep = self.replicas[pos]
        tr = self.tracer
        if tr.enabled:
            # health transitions land on the AFFECTED replica's lane so
            # a flight-recorder dump of that lane ends with them
            tr.event(
                "health", lane=f"replica{rep.index}", replica=rep.index,
                state=transition,
                error=str(self.health[pos].last_error or "")[:200],
            )
        if transition == "suspect":
            self.stats.replica_suspect += 1
            self._log.warning(
                "replica %d SUSPECT: %s", rep.index,
                self.health[pos].last_error,
            )
        elif transition == "recovered":
            self.stats.replica_recoveries += 1
            self._log.warning("replica %d recovered (circuit closed)",
                              rep.index)
        elif transition == "down":
            self.stats.replica_down += 1
            # capture the machine's recorded trip BEFORE failover runs
            # (_adopt_standby may replace the health record)
            down_at = self.health[pos].down_at_step
            self._on_replica_down(pos, exc)
            if self.flight_recorder is not None:
                self.flight_recorder.dump(
                    f"replica{rep.index}", "replica_down",
                    step=self._step_counter,
                    extra={
                        "replica_index": rep.index,
                        "health_state": HealthState.DOWN.value,
                        "down_at_step": down_at,
                    },
                )

    def _on_replica_down(self, pos: int,
                         exc: Optional[BaseException]) -> None:
        """The breaker opened: fail every request on the replica over
        to survivors (recompute re-admission), drop its session pins
        (they re-pin — which also re-seeds its prefix families on
        survivors), and tear its scheduler state down so a later probe
        re-admission starts clean."""
        rep = self.replicas[pos]
        self._log.warning(
            "replica %d DOWN (%s) — failing over its requests",
            rep.index, exc if exc is not None else
            self.health[pos].last_error,
        )
        if rep.index in self._draining:
            # died mid-drain: the DOWN path owns it now (failover +
            # standby adoption); the scale_in never commits and its
            # journaled begin recovers as "never happened"
            self._draining.discard(rep.index)
        self._drop_sessions(pos)
        victims = [
            cr for cr in self.requests.values()
            if cr.rid is not None and cr.replica == pos
            and cr.status not in TERMINAL_STATUSES
        ]
        for cr in victims:
            req = rep.rm.requests[cr.rid]
            # the host token list only ever holds FLUSHED truth — the
            # recompute re-admission regenerates anything in flight
            cr._known = list(req.tokens)
            cr.rid = None
            cr.replica = None
            cr.phase = "single"
            self._schedule_failover(cr)
        # queued migrations whose source died are failover victims now
        self._migration_queue = [
            c for c in self._migration_queue
            if self.requests[c].rid is not None
        ]
        self._mig_queued = set(self._migration_queue)
        try:
            rep.abandon()
        except Exception as abandon_exc:  # the pool may be torn mid-step
            self._log.warning(
                "replica %d abandon() failed (%s) — its pool is "
                "excluded from audits until it recovers",
                rep.index, abandon_exc,
            )
        if self.standbys:
            self._adopt_standby(pos)

    def _adopt_standby(self, pos: int) -> None:
        """A warm standby takes the dead replica's routing position:
        the dead replica's prefix radix tree — block keys + page bytes,
        host-spilled pages included — ships over the transport and
        re-admits on the standby (best-effort: an unreachable process
        means a COLD join, capacity is still replaced), then the
        standby enters routing at ``pos``. The dead replica retires
        permanently (its health record is replaced by the standby's
        fresh one, so it never probes back) — failover re-admissions
        and re-pinned sessions land on a warm tree instead of survivors
        re-seeding the families cold."""
        dead = self.replicas[pos]
        standby = self.standbys.pop(0)
        blocks = 0
        try:
            entries = dead.export_prefix_tree()
            if entries:
                blocks = standby.import_prefix_tree(entries)
        except Exception as exc:
            self._log.warning(
                "standby adoption: prefix-tree export from dead replica "
                "%d failed (%s) — standby %d joins COLD",
                dead.index, exc, standby.index,
            )
        self.replicas[pos] = standby
        try:
            rpos = self._routing_pos.index(pos)
        except ValueError:
            rpos = None
        if rpos is not None:
            self.router.replicas[rpos] = standby
        # a fresh health record: the standby starts HEALTHY and the
        # retired replica can never probe back into this position
        self.health.replicas[pos] = ReplicaHealth(pos, self.health.cfg)
        self._retired.append(dead)
        self.stats.standby_adoptions += 1
        self._log.warning(
            "standby replica %d adopted position %d (%d prefix blocks "
            "warm; %d standbys remain)",
            standby.index, pos, blocks, len(self.standbys),
        )

    def _schedule_failover(self, cr: ClusterRequest) -> None:
        """Bounded retries with exponential (cluster-step) backoff; past
        the bound the request fails terminally — never a hang."""
        cr.retries += 1
        self.stats.retries += 1
        if cr.retries > self.serving.failover_retries:
            cr.error = (
                f"replica failed and failover retries exhausted "
                f"({cr.retries - 1} re-admissions, failover_retries="
                f"{self.serving.failover_retries})"
            )
            self.stats.failover_errors += 1
            tr = self.tracer
            if tr.enabled:
                tr.event("request_error", trace_id=cr.cluster_id,
                         reason="failover_exhausted")
            if self.flight_recorder is not None:
                self.flight_recorder.dump(
                    self.tracer.lane or "router", "request_error",
                    step=self._step_counter,
                    extra={"cluster_id": cr.cluster_id,
                           "error": cr.error[:500]},
                )
            return
        backoff = (
            0 if cr.retries == 1
            else self.serving.failover_backoff_steps
            * (2 ** (cr.retries - 2))
        )
        cr._retry_at_step = self._step_counter + backoff
        self._failovers.append(cr.cluster_id)

    def _run_failovers(self) -> bool:
        """Re-admit requests whose backoff expired. A request that
        cannot be placed (no healthy replica) fails terminally."""
        if not self._failovers:
            return False
        progressed = False
        still: List[int] = []
        for cid in self._failovers:
            cr = self.requests[cid]
            if cr.error is not None or cr.rid is not None:
                continue
            if self._step_counter < cr._retry_at_step:
                still.append(cid)
                continue
            try:
                placed = self._place(cr, cr._known, ignore_slo=True)
            except Exception as exc:
                # the chosen home refused the submission (e.g. a
                # recovered manager re-admitting onto a replica whose
                # server died with the old manager, before the gap
                # detector trips it) — a health observation + another
                # bounded retry, never an exception out of the drive
                # loop
                pos = cr.replica
                cr.rid = None
                cr.replica = None
                if pos is not None:
                    self._observe_failure(pos, exc, self._step_counter)
                self._schedule_failover(cr)
                progressed = True
                continue
            if placed:
                self.stats.failovers += 1
                progressed = True
                tr = self.tracer
                if tr.enabled:
                    tr.event(
                        "failover", trace_id=cid,
                        replica=cr.profile.failover_replica_id,
                        retry=cr.retries,
                    )
                self._log.warning(
                    "failover: request %d re-admitted on replica %d "
                    "(retry %d, %d tokens recomputed)",
                    cid, cr.profile.failover_replica_id, cr.retries,
                    len(cr.tokens),
                )
            else:
                self.stats.failover_errors += 1
                progressed = True
        self._failovers = still
        return progressed

    # ------------------------------------------------------------------
    # prefill→decode migration (bounded queue + back-pressure)

    def _queue_migrations(self) -> None:
        """Move newly completed held prefills into the migration FIFO
        (finishing the ones that owe no decode phase), then apply the
        back-pressure budget: entries past it release their held pages
        and drain through recompute re-admission instead of parking."""
        for cid, cr in list(self.requests.items()):
            if (
                cr.phase != "prefill" or cr.rid is None
                or cid in self._mig_queued
            ):
                continue
            src = self.replicas[cr.replica]
            req = src.rm.requests[cr.rid]
            if req.status not in TERMINAL_STATUSES or req.pipeline_refs:
                continue
            if req.status is RequestStatus.ERROR:
                # unservable on the prefill pool (PR-2 ERROR path) — the
                # cluster request is terminal with that error
                src.rm.release_held(cr.rid)
                cr.phase = "single"
                continue
            done = len(req.tokens) >= self.serving.max_sequence_length
            if req.tokens[req.prompt_len:]:
                last = req.tokens[-1]
                stops = set(cr.gen.stop_token_ids)
                if self.eos_token_id is not None:
                    stops.add(self.eos_token_id)
                remaining = cr.gen.max_new_tokens - (
                    len(req.tokens) - cr.prompt_len
                )
                done = done or last in stops or remaining <= 0
            if done:
                # 1-token budget, a stop token, or max length — no
                # decode phase owed: it finished on the prefill replica
                src.rm.release_held(cr.rid)
                cr.phase = "single"
                continue
            self._migration_queue.append(cid)
            self._mig_queued.add(cid)
        budget = self.serving.migration_queue_budget
        if budget is not None:
            while len(self._migration_queue) > budget:
                # newest entries overflow (FIFO heads keep their pages —
                # they hand off next); the overflow recomputes instead
                cid = self._migration_queue.pop()
                self._mig_queued.discard(cid)
                self.stats.migration_queue_overflows += 1
                self._recompute_readmit(cid)
        depth = len(self._migration_queue)
        self.stats.migration_queue_depth = depth
        self.stats.migration_queue_peak = max(
            self.stats.migration_queue_peak, depth
        )

    def _drain_migration_queue(self) -> bool:
        """Hand queued prefills to the decode pool: page migration when
        a healthy decode replica has capacity; recompute re-admission
        when the decode pool is gone or a migration keeps failing."""
        if not self._migration_queue:
            return False
        progressed = False
        remaining_q: List[int] = []
        for cid in self._migration_queue:
            cr = self.requests[cid]
            if cr.rid is None or cr.error is not None:
                continue  # source died — the failover path owns it now
            if self._step_counter < cr._retry_at_step:
                remaining_q.append(cid)  # migration-failure backoff
                continue
            src = self.replicas[cr.replica]
            req = src.rm.requests[cr.rid]
            dsts = [r for r in self.decode_pool if self._routable_rep(r)]
            if not dsts:
                # decode pool entirely DOWN: fall back to
                # non-disaggregated serving on the surviving pool —
                # recompute re-admission frees the held pages and the
                # prefill replica (or any survivor) serves the decode
                # phase itself
                self._recompute_readmit(cid)
                progressed = True
                continue
            dst = min(
                dsts,
                key=lambda r: (r.queue_delay_s(), r.load(), r.index),
            )
            # the decode side runs the REMAINING budget: after a
            # failover the home's prompt already carries generated
            # tokens, and the dst counts generation from its own
            # adopted baseline (= the home's prompt_len)
            gen_dst = dataclasses.replace(
                cr.gen,
                max_new_tokens=cr.gen.max_new_tokens
                - (req.prompt_len - cr.prompt_len),
            )
            try:
                rid_dst = migrate_request(
                    src, dst, cr.rid, gen_dst,
                    stats=self.stats, injector=self.fault_injector,
                    trace_id=cr.cluster_id, tracer=self.tracer,
                )
            except Exception as exc:
                self.stats.migration_failures += 1
                cr.mig_attempts += 1
                self._log.warning(
                    "migration of request %d -> replica %d failed "
                    "(attempt %d): %s", cid, dst.index,
                    cr.mig_attempts, exc,
                )
                if cr.mig_attempts > self.serving.failover_retries:
                    self._recompute_readmit(cid)
                else:
                    cr._retry_at_step = self._step_counter + (
                        self.serving.failover_backoff_steps
                        * (2 ** (cr.mig_attempts - 1))
                    )
                    remaining_q.append(cid)
                progressed = True
                continue
            if rid_dst is None:
                remaining_q.append(cid)  # dst full right now — waits
                continue
            src.rm.release_held(cr.rid)
            cr.replica = self.replicas.index(dst)
            cr.rid = rid_dst
            cr.phase = "decode"
            cr.profile.replica_id = dst.index
            progressed = True
        self._migration_queue = remaining_q
        self._mig_queued = set(remaining_q)
        self.stats.migration_queue_depth = len(remaining_q)
        return progressed

    def _recompute_readmit(self, cid: int) -> None:
        """Drain one held prefill WITHOUT moving pages: release the
        hold (its pages free immediately) and resubmit prompt + first
        token through the recompute path on the best surviving replica
        — the decode pool when any of it is healthy, else any healthy
        replica. The re-prefill is the back-pressure price (warm where
        prefix caching holds the prompt); greedy outputs stay bitwise."""
        cr = self.requests[cid]
        src = self.replicas[cr.replica]
        req = src.rm.requests[cr.rid]
        known = list(req.tokens)
        src.rm.release_held(cr.rid)
        cr.rid = None
        cr.replica = None
        cr.phase = "single"
        cr.retries += 1
        self.stats.retries += 1
        cands = [r for r in self.decode_pool if self._routable_rep(r)] or [
            r for r in self.replicas if self._routable_rep(r)
        ]
        if not cands:
            cr._known = known
            cr.error = (
                "no healthy replica to drain the held prefill to — "
                "the request fails terminally instead of parking"
            )
            self.stats.failover_errors += 1
            return
        rep = min(
            cands, key=lambda r: (r.queue_delay_s(), r.load(), r.index)
        )
        produced = len(known) - cr.prompt_len
        gen_home = dataclasses.replace(
            cr.gen, max_new_tokens=cr.gen.max_new_tokens - produced
        )
        cr.rid = rep.rm.submit(known, gen_home, trace_id=cr.cluster_id)
        cr.replica = self.replicas.index(rep)
        rep.rm.requests[cr.rid].profile = cr.profile
        cr.profile.retries = cr.retries
        cr.profile.failover_replica_id = rep.index
        cr.profile.replica_id = rep.index
        tr = self.tracer
        if tr.enabled:
            tr.event("recompute_readmit", trace_id=cid,
                     replica=rep.index, n_tokens=len(known))
        self._log.debug(
            "migration back-pressure: request %d drained to replica %d "
            "via recompute (%d tokens re-prefill)",
            cid, rep.index, len(known),
        )

    # ------------------------------------------------------------------
    # the drive loop

    def _observe_failure(self, pos: int, exc: BaseException,
                         step_no: int) -> None:
        """ONE health failure observation per replica per cluster step
        — an RPC-erroring replica that is also inside a heartbeat gap
        must not burn through ``failure_threshold`` twice as fast as a
        plain crashing one (the PR-9 arithmetic is the contract)."""
        if pos in self._failed_obs:
            return
        self._failed_obs.add(pos)
        self._note_transition(
            pos, self.health[pos].record_failure(exc, step_no), exc
        )

    def _check_gap(self, pos: int, rep, step_no: int) -> None:
        """Heartbeat-gap detection, in deterministic CLUSTER steps: no
        successful exchange for ``heartbeat_gap_steps`` steps is a
        SUSPECT observation each step until contact resumes (or the
        breaker trips)."""
        gap = step_no - rep.last_contact_step
        if gap >= self.serving.heartbeat_gap_steps:
            self.stats.heartbeat_gaps += 1
            tr = self.tracer
            if tr.enabled:
                tr.event("heartbeat_gap", replica=rep.index, gap=gap)
            self._observe_failure(
                pos,
                HeartbeatGap(
                    f"replica {rep.index}: no successful exchange for "
                    f"{gap} cluster steps"
                ),
                step_no,
            )

    def _heartbeat_remote(self, pos: int, rep, step_no: int) -> None:
        """Idle remote replicas stay observable: a heartbeat every
        ``heartbeat_interval_steps`` refreshes the telemetry mirror
        (SchedulerStats + the queue-delay inputs the router reads) and
        stamps contact; a FAILED heartbeat is silent on its own (the
        loss is retried/absorbed at the transport) — sustained loss
        surfaces through :meth:`_check_gap`."""
        due = (
            step_no - rep.last_contact_step
            >= self.serving.heartbeat_interval_steps
        )
        if due and rep.heartbeat():
            rep.last_contact_step = step_no
            return
        self._check_gap(pos, rep, step_no)

    def _step_replicas_serial(self, step_no: int) -> bool:
        """The original one-RPC-at-a-time drive loop — kept verbatim as
        the reference arm (``ServingConfig.concurrent_stepping=False``,
        and what the in-process cluster runs): the concurrent loop's
        contract is to be indistinguishable from THIS."""
        progressed = False
        for pos in range(len(self.replicas)):
            rep = self.replicas[pos]
            h = self.health[pos]
            if h.state is HealthState.DOWN:
                if h.maybe_probe(step_no):
                    self.stats.probes += 1
                    if self.tracer.enabled:
                        self.tracer.event("probe", replica=rep.index,
                                          backoff=h.backoff_steps)
                    self._log.warning(
                        "replica %d probing (circuit half-open after "
                        "%d-step backoff)", rep.index, h.backoff_steps,
                    )
                    progressed = True
                else:
                    continue
            remote = getattr(rep, "is_remote", False)
            if not rep.has_work():
                if remote:
                    self._heartbeat_remote(pos, rep, step_no)
                continue
            t0 = time.perf_counter()
            try:
                stepped = rep.step()
            except Exception as exc:
                self.stats.step_faults += 1
                self._observe_failure(pos, exc, step_no)
                if (
                    remote and rep is self.replicas[pos]
                    and self.health[pos].state is not HealthState.DOWN
                ):
                    self._check_gap(pos, rep, step_no)
                progressed = True
                continue
            if remote:
                rep.last_contact_step = step_no
                self.stats.note_rpc_rtt_ms(
                    rep.index, (time.perf_counter() - t0) * 1000.0
                )
            latency = (time.perf_counter() - t0) + rep.injected_latency_s
            self._note_transition(
                pos, h.record_success(latency, step_no, had_work=True)
            )
            progressed = stepped or progressed
        return progressed

    def _step_replicas_concurrent(self, step_no: int) -> bool:
        """Fan-out drive loop: ISSUE every routable replica's step RPC
        (and every due idle-replica heartbeat) without blocking, then
        HARVEST and apply results in replica-index order — N wire
        round-trips overlap into one (O(RTT), not O(N·RTT)).

        Determinism contract: completion order NEVER changes cluster
        behavior. Issue runs in replica-index order and only touches
        per-replica state (fault kinds fire at the serial loop's call
        site; ``has_work``/heartbeat-due reads are position-local, and
        nothing the apply phase mutates — health transitions, failover
        enqueues, migration queues — feeds back into another position's
        issue decision inside the same step; those all settle AFTER the
        loop, exactly as in the serial arm). Apply runs in
        replica-index order on the manager's thread, so the PR-9 health
        machine, the one-observation-per-step guard, failover order and
        journal semantics see the SAME sequence of observations the
        serial loop produced, no matter how responses interleaved on
        the wire."""
        progressed = False
        plan: list = []  # (pos, rep, kind, payload) in replica order
        inflight = 0
        for pos in range(len(self.replicas)):
            rep = self.replicas[pos]
            h = self.health[pos]
            if h.state is HealthState.DOWN:
                if h.maybe_probe(step_no):
                    self.stats.probes += 1
                    if self.tracer.enabled:
                        self.tracer.event("probe", replica=rep.index,
                                          backoff=h.backoff_steps)
                    self._log.warning(
                        "replica %d probing (circuit half-open after "
                        "%d-step backoff)", rep.index, h.backoff_steps,
                    )
                    progressed = True
                else:
                    continue
            remote = getattr(rep, "is_remote", False)
            if not rep.has_work():
                if remote:
                    due = (
                        step_no - rep.last_contact_step
                        >= self.serving.heartbeat_interval_steps
                    )
                    if due:
                        plan.append(
                            (pos, rep, "hb", rep.heartbeat_async())
                        )
                        inflight += 1
                    else:
                        plan.append((pos, rep, "gap", None))
                continue
            t0 = time.perf_counter()
            if not remote:
                # no wire to overlap — the local step runs where the
                # serial loop ran it, its outcome applies in order
                try:
                    stepped = rep.step()
                except Exception as exc:
                    plan.append((pos, rep, "step_fail", exc))
                else:
                    lat = (
                        (time.perf_counter() - t0)
                        + rep.injected_latency_s
                    )
                    plan.append((pos, rep, "step_done", (stepped, lat)))
                continue
            try:
                call = rep.step_async()
            except Exception as exc:
                # replica-kind fault / abandon replay failed at issue —
                # the serial loop's step() raised at the same point
                plan.append((pos, rep, "step_fail", exc))
            else:
                plan.append((pos, rep, "step", (t0, call)))
                inflight += 1
        if inflight > self.stats.rpc_inflight_peak:
            self.stats.rpc_inflight_peak = inflight
        for pos, rep, kind, payload in plan:
            if kind == "gap":
                self._check_gap(pos, rep, step_no)
            elif kind == "hb":
                if rep.finish_heartbeat(payload):
                    rep.last_contact_step = step_no
                else:
                    self._check_gap(pos, rep, step_no)
            elif kind == "step_fail":
                progressed = self._apply_step_failure(
                    pos, rep, payload, step_no
                ) or progressed
            elif kind == "step_done":
                stepped, latency = payload
                self._note_transition(
                    pos,
                    self.health[pos].record_success(
                        latency, step_no, had_work=True
                    ),
                )
                progressed = stepped or progressed
            else:  # "step" — harvest the remote ticket
                t0, call = payload
                try:
                    stepped = rep.finish_step(call)
                except Exception as exc:
                    progressed = self._apply_step_failure(
                        pos, rep, exc, step_no
                    ) or progressed
                    continue
                rep.last_contact_step = step_no
                done = (
                    call.completed_at if call.completed_at is not None
                    else time.perf_counter()
                )
                self.stats.note_rpc_rtt_ms(
                    rep.index, max(0.0, done - t0) * 1000.0
                )
                latency = max(0.0, done - t0) + rep.injected_latency_s
                self._note_transition(
                    pos,
                    self.health[pos].record_success(
                        latency, step_no, had_work=True
                    ),
                )
                progressed = stepped or progressed
        return progressed

    def _apply_step_failure(self, pos: int, rep, exc: BaseException,
                            step_no: int) -> bool:
        """The serial loop's step-exception arm, shared by the
        concurrent loop's issue and harvest phases — one failure
        observation (guarded per step), plus the gap check for a
        still-installed remote that is not yet DOWN."""
        self.stats.step_faults += 1
        self._observe_failure(pos, exc, step_no)
        if (
            getattr(rep, "is_remote", False)
            and rep is self.replicas[pos]
            and self.health[pos].state is not HealthState.DOWN
        ):
            self._check_gap(pos, rep, step_no)
        return True

    def step(self) -> bool:
        """One cluster step: advance every steppable replica under the
        health monitor (remote replicas additionally heartbeat when
        idle, with gap detection in cluster steps), settle
        prefill→decode migrations, then run any due failover
        re-admissions. Returns False when no replica has work left and
        nothing is pending recovery.

        With ``ServingConfig.concurrent_stepping`` (the default) and
        any remote members, the per-replica RPCs fan out concurrently
        and the step costs ~one round-trip; results still apply in
        replica-index order (see :meth:`_step_replicas_concurrent` for
        the determinism contract)."""
        t_step = time.perf_counter()
        self._step_counter += 1
        step_no = self._step_counter
        if self.fault_injector is not None:
            # scripted manager death (FaultPlan "manager_crash"): the
            # checkpoint-kill raises HERE, before any replica steps —
            # the test/bench recovers from the journal where a real
            # SIGKILL would restart the process
            self.fault_injector.on_cluster_step(self)
        tr = self.tracer
        if tr.enabled and self._pending_trace:
            # recovery ran before a tracer could attach — its
            # recover/replay events flush on the first traced step
            for name, kw in self._pending_trace:
                tr.event(name, **kw)
            self._pending_trace = []
        self._failed_obs = set()
        concurrent = (
            getattr(self.serving, "concurrent_stepping", True)
            and any(getattr(r, "is_remote", False) for r in self.replicas)
        )
        if concurrent:
            progressed = self._step_replicas_concurrent(step_no)
        else:
            progressed = self._step_replicas_serial(step_no)
        if self.disaggregated:
            self._queue_migrations()
            progressed = self._drain_migration_queue() or progressed
        progressed = self._run_failovers() or progressed
        progressed = _maybe_retire(self) or progressed
        if self._failovers or self._migration_queue:
            # pending recoveries keep the drive loop alive through their
            # backoff windows — a generate() must never break out and
            # strand a request between homes
            progressed = True
        # completion sweep + autoscale BEFORE the journal sync: a
        # policy decision's records (and the scale ops' begin records)
        # batch into the same durable flush as the step that made them
        self._sweep_completions()
        if self.autoscaler is not None:
            self.autoscaler.on_step(step_no)
        # journal sync point: flushed-token deltas + newly terminal
        # records batch into ONE buffered write + file flush per step
        self._journal_sync()
        self.stats.note_cluster_step_ms(
            (time.perf_counter() - t_step) * 1000.0
        )
        if step_no % 200 == 0:
            self._log.debug(
                "%s", self.stats.report([r.rm.stats for r in self.replicas])
            )
        return progressed

    def drain(self) -> None:
        """Flush every healthy replica's pipeline, then settle any
        migrations those flushes unblocked (a prefill pass whose
        completion was still in the pipeline hands its pages off here;
        the adopted decode work itself is driven by later :meth:`step`
        calls, same as RequestManager.drain never runs new steps). A
        flush failure is a replica failure — same health path as a
        step exception."""
        for pos, rep in enumerate(self.replicas):
            if self.health[pos].state is HealthState.DOWN:
                continue
            try:
                rep.drain()
            except Exception as exc:
                self.stats.step_faults += 1
                self._note_transition(
                    pos,
                    self.health[pos].record_failure(exc, self._step_counter),
                    exc,
                )
        if self.disaggregated:
            self._queue_migrations()
            self._drain_migration_queue()
        self._run_failovers()
        _maybe_retire(self)
        self._sweep_completions()
        self._journal_sync()

    def _sweep_completions(self) -> None:
        """Settle per-replica completion accounting for requests that
        went terminal since the last sweep: counters on ClusterStats,
        and ``(prompt_len, output_len)`` pairs into the completion
        window the autotune TrafficEstimator drains. Errored requests
        leave the open set but do NOT enter the window — a shed
        request's zero-length output is not a service-time sample."""
        if not self._open_cids:
            return
        closed = []
        for cid in self._open_cids:
            cr = self.requests.get(cid)
            if cr is None:
                closed.append(cid)
                continue
            st = cr.status
            if st not in TERMINAL_STATUSES:
                continue
            closed.append(cid)
            if st is RequestStatus.ERROR:
                continue
            produced = len(cr.output_tokens)
            self._completion_window.append((cr.prompt_len, produced))
            rep_idx = int(cr.profile.replica_id)
            if rep_idx >= 0:
                self.stats.note_completion(rep_idx)
        for cid in closed:
            self._open_cids.discard(cid)
        # bound the window even if nobody drains it (no autoscaler)
        if len(self._completion_window) > 4096:
            del self._completion_window[:-4096]

    def drain_completion_window(self) -> List[Tuple[int, int]]:
        """Hand over (and clear) the ``(prompt_len, output_len)`` pairs
        of requests that finished since the last call — the autotune
        TrafficEstimator's per-observation completion feed."""
        window, self._completion_window = self._completion_window, []
        return window

    def _attach_autoscaler(self) -> None:
        # lazy import: serve.cluster must not depend on serve.autotune
        # at import time (autotune imports the cost model stack)
        from ..autotune.policy import Autoscaler

        self.autoscaler = Autoscaler.from_manager(self)

    # ------------------------------------------------------------------
    # results

    def cluster_stats(self) -> Dict[str, object]:
        """ClusterStats snapshot over the live per-replica stats."""
        return self.stats.snapshot([r.rm.stats for r in self.replicas])

    def health_snapshot(self) -> List[str]:
        return self.health.snapshot()

    def check_no_leaks(self) -> None:
        """Page-pool audits on every replica that is NOT circuit-broken
        — a DOWN replica's pool is unreachable (on multi-host it is
        gone with the process), not leaked; it re-enters the audit set
        the moment it probes back."""
        for pos, rep in enumerate(self.replicas):
            if self.health[pos].state is HealthState.DOWN:
                continue
            rep.check_no_leaks()

    def result(self, cid: int) -> GenerationResult:
        cr = self.requests[cid]
        out = cr.output_tokens
        text = (
            self.tokenizer.decode(out) if self.tokenizer is not None else ""
        )
        error = cr.error
        if error is None and cr.rid is not None:
            error = self.replicas[cr.replica].rm.requests[cr.rid].error
        return GenerationResult(
            request_id=cid,
            prompt=cr.prompt_text,
            input_tokens=list(cr.tokens),
            output_tokens=list(out),
            output_text=text,
            profile=cr.profile,
            error=error,
        )

    def _terminal(self, cid: int) -> bool:
        return self.requests[cid].status in TERMINAL_STATUSES

    def generate(
        self,
        prompts: Union[str, Sequence[Union[str, Sequence[int]]]],
        gen: Optional[GenerationConfig] = None,
        max_new_tokens: Optional[int] = None,
        session_ids: Optional[Sequence[object]] = None,
    ) -> List[GenerationResult]:
        """Blocking generate across the cluster (router-placed)."""
        if isinstance(prompts, str):
            prompts = [prompts]
        cids = [
            self.submit(
                p, gen, max_new_tokens,
                session_id=session_ids[i] if session_ids else None,
            )
            for i, p in enumerate(prompts)
        ]
        while any(not self._terminal(c) for c in cids):
            if not self.step():
                break
        self.drain()
        return [self.result(c) for c in cids]

    def generate_stream(
        self,
        prompts: Union[str, Sequence[Union[str, Sequence[int]]]],
        gen: Optional[GenerationConfig] = None,
        max_new_tokens: Optional[int] = None,
        session_ids: Optional[Sequence[object]] = None,
    ) -> Iterator[StreamEvent]:
        """Streaming generate across the cluster: one StreamEvent per
        drained token (``request_id`` is the CLUSTER id) + a terminal
        event per request (``error`` set for sheds/failures). Token
        counts are monotone across a migration — the first output token
        is visible on both sides of the hand-off, so nothing is dropped
        or re-sent — and across a failover: the re-admission's known
        tokens are exactly the flushed (= streamed) prefix, so the
        stream resumes where it stopped."""
        if isinstance(prompts, str):
            prompts = [prompts]
        cids = [
            self.submit(
                p, gen, max_new_tokens,
                session_id=session_ids[i] if session_ids else None,
            )
            for i, p in enumerate(prompts)
        ]
        sent = {c: 0 for c in cids}
        finished: set = set()

        def drain_events():
            for c in cids:
                if c in finished:
                    continue
                cr = self.requests[c]
                out = cr.output_tokens
                while sent[c] < len(out):
                    tok = out[sent[c]]
                    sent[c] += 1
                    yield StreamEvent(c, int(tok))
                if self._terminal(c):
                    finished.add(c)
                    err = cr.error
                    if err is None and cr.rid is not None:
                        home = self.replicas[cr.replica].rm
                        err = home.requests[cr.rid].error
                    yield StreamEvent(c, None, done=True, error=err)

        while len(finished) < len(cids):
            progressed = self.step()
            yield from drain_events()
            if not progressed and len(finished) < len(cids):
                self.drain()
                yield from drain_events()
                if len(finished) < len(cids):
                    break  # nothing schedulable remains — avoid spinning
        self.drain()
        yield from drain_events()

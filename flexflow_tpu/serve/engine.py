"""InferenceEngine — compiled-step management for serving.

TPU-native counterpart of the reference ``InferenceManager`` (reference
``src/runtime/inference_manager.cc:81-708``): where the reference compiles
the op graph per inference mode, assigns MachineViews per pipeline stage
and allocates/reuses activation buffers, we jit one step function per
static signature (chunk size × logits mode × mask mode) over a device
mesh, with the KV cache donated through every call so steady-state
decoding allocates nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.mesh import DATA_AXIS, MachineSpec, set_mesh as _set_mesh
from ..obs import sublayers
from ..obs.builds import BuildLog
from ..obs.sublayers import sublayer
from ..obs.tracer import NULL_TRACER
from .batch_config import BatchConfig
from .sampling import choose_sample_mode, sample_tokens


@dataclasses.dataclass
class ServingConfig:
    """Serving limits (reference batch_config.h:58-60 + RequestManager
    setters, request_manager.h)."""

    max_requests_per_batch: int = 16
    max_sequence_length: int = 2048
    prefill_chunk: int = 128
    max_spec_tree_tokens: int = 64
    cache_dtype: Any = jnp.bfloat16
    # "xla" (default) or "pallas": what the PAGED step runs on — the
    # Pallas kernels of serve/kernels.py (ragged paged attention, the
    # grouped expert matmuls, a family's own), or their XLA twins. The
    # dense layout is the XLA reference layout and takes "xla" alone.
    kernels: str = "xla"
    # Steady-state decode keeps up to this many steps in flight: sampled
    # tokens feed the next step on-device, the host fetches results one
    # step behind (the reference's 4-deep batch-future pipeline,
    # request_manager.cc:2310-2325).
    dispatch_ahead: int = 4
    # Iteration-level continuous batching: prefill chunks ride in the
    # SAME pipelined step as decode rows (one jitted "mixed step" with
    # on-device sampling for decode rows and prefill-final rows), so
    # admissions, chunk progression and completions never drain the
    # dispatch-ahead pipeline. False restores the flush-on-admit
    # scheduler (any PREFILLING request forces the blocking sync path) —
    # kept as the bench baseline and an escape hatch.
    continuous_batching: bool = True
    # Per-step chunked-prefill token budget of the mixed step: each
    # prefilling slot contributes at most this many NEW prompt tokens
    # per iteration (decode rows are not budgeted — they always get
    # their one token). It is the mixed step's compiled row width
    # C = min(prefill_chunk, max_tokens_per_step), so it directly bounds
    # the compute (R×C) — and therefore the latency — a joining prompt
    # adds to in-flight decodes, Sarathi/vLLM-style: small mixed steps
    # keep decode throughput high under churn, at the cost of slower
    # prompt ingestion. The cap is per ROW, not across rows: the padded
    # (R, C) step pays R×C compute regardless of how many rows carry
    # prefill tokens, so limiting the number of prefilling rows per step
    # would save nothing. 0 (default) = a full prefill_chunk per row.
    max_tokens_per_step: int = 0
    # Serving-triage dump directory (reference inference_debugging,
    # serve/__init__.py:48 — per-op inputs/outputs saved to file): every
    # engine step additionally runs an eager per-layer forward and
    # writes each layer's hidden states + the step's tokens/positions as
    # .npy. None = off; the FF_INFERENCE_DEBUGGING env var (a directory
    # path) switches it on without touching code.
    inference_debugging: Optional[str] = None
    # KV cache layout. "dense": per-slot (slots, max_len+1) lines — HBM
    # scales with the worst case. "paged": fixed-size token pages + a
    # per-slot page table (Ragged Paged Attention, PAPERS.md arxiv
    # 2604.15464) — HBM scales with pages actually allocated, which is
    # what lets one chip run the reference's 64 request slots.
    kv_layout: str = "dense"
    page_size: int = 128                    # tokens per KV page
    # Page-pool budget in tokens (rounded up to whole pages). None =
    # worst case (slots × pages_per_slot — same capacity as dense, still
    # allocated lazily). Set it below the worst case to oversubscribe:
    # the RequestManager preempts (recompute-on-readmit) on exhaustion.
    max_cached_tokens: Optional[int] = None
    # Quantized paged KV pages (serve/kv_quant.py; paged layout only).
    # "int8": pages store int8 codes + per-page-per-KV-head f32 amax
    # scales; serve_step's KV write quantizes in the step and attention
    # dequantizes at read time (fused into the Pallas ragged paged
    # kernel), so full-precision K/V never round-trip HBM. "int4":
    # packed nibbles — two codes per byte along head_dim, unpacked in
    # VMEM by the same kernel (logit tolerance is wider than int8's;
    # README "Hierarchical KV cache" documents both). The
    # max_cached_tokens budget keeps meaning "this much KV HBM": the
    # same budget buys ~2x (int8) / ~4x (int4) the pages
    # (kv_quant.quantized_pool_pages; ≥1.9x / ≥3.8x measured after
    # scale rows). None (default) = full-precision cache_dtype pages.
    kv_quant: Optional[str] = None
    # Automatic prefix caching (serve/prefix_cache.py, paged layout
    # only — a no-op passthrough on dense): finished requests' prompt
    # pages stay live in a radix tree; a new request whose prompt shares
    # a cached page-aligned prefix splices those pages into its table
    # and prefills only the uncached suffix. Cached-but-idle pages are
    # LRU-evicted before any allocation fails, so the cache never causes
    # a preemption a cold pool would not. Off by default: cached pages
    # intentionally outlive their requests, which changes the pool
    # accounting benchmarks/tests of the cold allocator assert on.
    prefix_caching: bool = False
    # Hierarchical KV cache — host-RAM spill tier for cold prefix
    # pages (serve/prefix_cache.py; requires prefix_caching): instead
    # of dropping an idle cached page under pool pressure, its content
    # (codes + scales) is copied to pinned host memory with an ASYNC
    # device→host DMA and the HBM page is freed; a later prompt that
    # matches the spilled prefix re-admits the page with an async
    # host→device copy before splice — a cache miss to HBM becomes a
    # host hit instead of a full prefill recompute. The value bounds
    # the host tier in bytes (its own LRU drops cold host pages past
    # it); None (default) = off, cold pages are simply evicted.
    # Spill→re-admit round-trips are byte-exact, so generation over a
    # re-admitted prefix is BITWISE the never-evicted warm path's
    # (tests/test_kv_hierarchy.py).
    host_cache_bytes: Optional[int] = None
    # Context-parallel long-context serving (ROADMAP item 5a; paged
    # layout only). "context": ONE request's KV pages are sharded
    # across ``context_shards`` sequence shards — logical page j lives
    # on shard j % n (striped, so decode reads and long prompts
    # load-balance) and each shard owns its own slice of the pool, so
    # a prompt far beyond one shard's HBM budget serves at the
    # aggregate capacity n × max_cached_tokens. ``max_cached_tokens``
    # becomes a PER-SHARD budget and admission accounting goes
    # per-shard (a request is servable iff every shard can cover its
    # striped share). Attention over the sharded pool is ring ragged
    # paged attention (serve/kernels.ring_ragged_paged_attention): on
    # a mesh whose ``seq`` degree matches, each shard attends its
    # resident pages and partial softmax stats rotate via ppermute;
    # on a single-device mesh (this box) every "shard" is locally
    # addressable and the standard table gather IS the ring result —
    # bitwise the CP-off step, which is what keeps CP-on vs CP-off
    # generation BITWISE (tests/test_long_context.py). "none"
    # (default) = the single-pool layout, byte-for-byte unchanged.
    kv_shard: str = "none"
    # Number of context shards; 0 derives it from the mesh's ``seq``
    # axis degree. On a mesh with seq > 1 the two must agree.
    context_shards: int = 0
    # What gets published into the prefix tree: "complete" (default) —
    # the whole sequence, prompt + generated, at request completion (the
    # multi-turn case: the next turn's prompt extends this turn's
    # transcript); "prefill" — the prompt alone, as soon as its last
    # chunk is dispatched (concurrent same-prompt requests hit sooner).
    cache_policy: str = "complete"
    # Decode-step fusions to enable, each bitwise-identical to its
    # unfused counterpart (tests/test_fused_decode.py). One is left:
    #   "rope_kv_write" — RoPE on Q/K and the (optionally
    #     int8-quantizing) KV page write fold INSIDE the ragged paged
    #     Pallas kernel (serve/kernels.fused_rope_paged_attention), so
    #     fresh K/V never round-trip HBM between the step's projection
    #     and its attention read. Paged layout only; model families
    #     advertise support via their FUSED_DECODE tuple. With
    #     kernels="xla" the flag is a no-op — the unfused XLA step IS
    #     the CPU-parity fallback.
    # Off by default. (The sampling head is no fusion to ask for: every
    # step samples on the device with the head its batch's decode-head
    # arrays need, serve/sampling.choose_sample_mode.)
    fused_decode: Tuple[str, ...] = ()
    # Cluster serving (serve/cluster/): one process drives this many
    # engine replicas — each its own mesh and KV pool — behind a
    # front-end Router (prefix-cache-aware placement, session affinity,
    # SLO-aware load shedding). 1 (default) = the single-engine path,
    # byte-for-byte unchanged. The per-replica engine is cluster-blind:
    # every replica is built with this same ServingConfig and the
    # cluster fields only steer the ClusterManager above them.
    replicas: int = 1
    # Placement policy of the front-end router: "prefix" routes to the
    # replica whose radix tree holds the longest match on the incoming
    # prompt (falling back to least-loaded on a universal miss),
    # "round_robin" cycles, "least_loaded" picks the smallest
    # queue-delay estimate. Session affinity (submit(session_id=...))
    # overrides the policy for multi-turn chat whichever is chosen.
    router_policy: str = "prefix"
    # Disaggregated prefill/decode pools: the first ``prefill_replicas``
    # replicas only prefill, the remaining ``decode_replicas`` only
    # decode — a request prefills on a prefill-pool replica and its KV
    # pages MIGRATE to a decode-pool replica at the chunked-prefill
    # boundary (serve/cluster/migration.py: gather_page_kv →
    # scatter_page_kv, byte-exact, so disaggregated generation is
    # bitwise the single-replica path's). Both 0 (default) = every
    # replica serves both phases; when set they must sum to
    # ``replicas`` and the layout must be paged (pages are the unit
    # being shipped).
    prefill_replicas: int = 0
    decode_replicas: int = 0
    # SLO-aware admission: shed a request at the router when EVERY
    # eligible replica's queue-delay estimate (backlog tokens over its
    # observed token rate, serve/cluster/replica.py) exceeds this many
    # seconds. A shed surfaces as RequestStatus.ERROR /
    # GenerationResult.error — the PR-2 contract: terminal, never a
    # hang. None (default) = never shed.
    slo_queue_delay_s: Optional[float] = None
    # Fault tolerance (serve/cluster/health.py + manager failover):
    # when a replica is circuit-broken (DOWN), each of its in-flight
    # requests is re-admitted to a healthy replica through recompute
    # (prompt + tokens generated so far re-prefill — the vLLM-style
    # preemption path, so greedy generations stay bitwise the
    # fault-free run's). failover_retries bounds how many times ONE
    # request may be re-admitted before it turns into a terminal
    # GenerationResult.error (never a hang); repeat re-admissions back
    # off failover_backoff_steps × 2^(retries-2) cluster steps.
    failover_retries: int = 2
    failover_backoff_steps: int = 4
    # Migration back-pressure (disaggregated serving): at most this
    # many finished prefills may WAIT for decode-pool capacity holding
    # their slot + pages (ROADMAP item 1: a full decode pool must not
    # park held prefills unboundedly). Overflow entries release their
    # pages immediately and drain through recompute re-admission on the
    # decode pool's own pending queue instead. None (default) = no
    # bound — the PR-8 behavior.
    migration_queue_budget: Optional[int] = None
    # Replica RPC transport (serve/cluster/transport.py + remote.py).
    # "inproc" (default): replicas are driven by direct method calls —
    # the PR-8/9 in-process cluster, byte-for-byte unchanged.
    # "loopback": every Replica call round-trips the length-prefixed
    # binary wire codec in-process (encode → frame → decode → dispatch
    # → encode → decode) — the transported cluster is BITWISE the
    # in-process one (tests/test_transport.py), and all transport
    # machinery (deadlines, retries, heartbeats, gap detection,
    # transport fault kinds) runs for real. "socket": localhost TCP to
    # subprocess replica servers (python -m
    # flexflow_tpu.serve.cluster.server), one single-process JAX
    # runtime per replica — true multi-process serving that sidesteps
    # the CPU backend's missing multiprocess collectives; requires
    # replica_endpoints.
    replica_transport: str = "inproc"
    # "host:port" per remote replica (socket transport only): one entry
    # per replica, then one per warm standby, in position order.
    replica_endpoints: Tuple[str, ...] = ()
    # Warm-standby replicas (serve/cluster/manager.py): this many extra
    # pre-built engines sit OUTSIDE the routing set; when a routed
    # replica is circuit-broken (DOWN), a standby ADOPTS its position —
    # the dead replica's prefix-cache radix tree (block keys + page
    # bytes, host-spilled pages included) ships over the transport and
    # re-admits on the standby, which then joins routing in the dead
    # replica's place. Failover re-admissions land on a WARM tree
    # instead of survivors re-seeding the families cold. Export is
    # best-effort: a truly dead process (unreachable transport) makes
    # the standby join cold — capacity is still replaced. 0 = none
    # (the PR-9 behavior: survivors absorb the load).
    standby_replicas: int = 0
    # Every replica RPC's deadline in seconds (the socket timeout on
    # send + response read; injected "delay" faults at/over it fail the
    # attempt). A deadline expiry is retried like any transport error.
    rpc_deadline_s: float = 5.0
    # Bounded retries per RPC past the first attempt; retries reuse the
    # request's seq id and the server replays cached responses, so a
    # retried step/submit is at-most-once even when only the response
    # was lost. Exhausted retries surface the TransportError to the
    # drive loop — the same health observation path as a local step
    # exception.
    rpc_retries: int = 2
    # Wall-clock base of the exponential retry backoff (socket
    # transport only — the loopback fails or succeeds instantly, and
    # all HEALTH accounting stays in deterministic cluster steps).
    rpc_backoff_s: float = 0.02
    # Concurrent cluster stepping (the default): ClusterManager.step
    # fans the per-replica step RPCs (and due idle heartbeats) out to
    # every routable remote member at once and harvests them in
    # replica-index order — a cluster step costs ~one round-trip
    # instead of N. Completion order never changes behavior (health
    # observations, failover order and journal records apply in
    # replica-index order either way). False = the serial
    # one-RPC-at-a-time reference loop, kept as the bench A/B arm and
    # determinism oracle; in-process ("inproc") clusters always use it
    # (there is no wire latency to overlap).
    concurrent_stepping: bool = True
    # Elastic, crash-recoverable control plane (serve/cluster/
    # journal.py + reconfigure.py): a directory for the durable request
    # journal — an append-only, CRC-framed log of submissions,
    # flushed-token deltas (batched at the drive loop's flush sync
    # point; no hot-path fsync) and terminal records, plus the
    # membership snapshots live reconfiguration (scale_out / scale_in /
    # set_pools) commits. A SIGKILL'd ClusterManager restarts with
    # ``ClusterManager.recover(...)``: the journal replays (a torn tail
    # truncates, never corrupts), still-running subprocess replica
    # servers reconnect, and every unfinished request re-admits through
    # the recompute path with its journaled prompt + flushed prefix —
    # greedy outputs bitwise the uninterrupted run, zero lost or
    # duplicated requests. None (default) = no journal (a manager crash
    # strands in-flight requests, the pre-PR-14 behavior).
    journal_dir: Optional[str] = None
    # Idle remote replicas are heartbeated every this many cluster
    # steps (a step RPC counts as contact, so busy replicas never pay
    # a separate heartbeat); the response carries the SchedulerStats
    # snapshot + queue-delay inputs the router reads.
    heartbeat_interval_steps: int = 1
    # No successful exchange for this many CLUSTER steps = a heartbeat
    # gap: ONE health observation per gapped step (deduplicated against
    # same-step RPC-error observations — a replica that is both gapped
    # and erroring is observed once, preserving the PR-9 threshold
    # arithmetic). Counted in cluster steps, never wall clock.
    heartbeat_gap_steps: int = 4
    # Runtime hazard sanitizers (flexflow_tpu/analysis/): "retrace" — a
    # strict RetraceGuard on the engine's jit chokepoint that raises on
    # any step recompile after its first compile (the shape/dtype-drift
    # perf-bug class caught at test time instead of as a 100x TPU
    # slowdown); "retrace-warn" — record + FF_LOG=serve=debug log only;
    # "donation" — poison donated cache pytrees after every dispatch so
    # use-after-donate (the PR-2 page-corruption class) raises loudly;
    # "locks" — the process-global LockSanitizer watches every
    # SanitizableLock in the transport/server stack (acquisition-order
    # graph, per-thread held stacks) and raises LockOrderInversion on
    # the A->B / B->A deadlock recipe at the second acquisition.
    # Off by default (zero steady-state overhead); tests and bench flip
    # them on, and FF_SANITIZERS=retrace,donation,locks enables them
    # from the environment without touching code.
    sanitizers: Tuple[str, ...] = ()
    # Self-driving serving (serve/autotune/policy.py): None (default) =
    # no policy loop; "drive" = a cost-model Autoscaler rides
    # ClusterManager.step and APPLIES journaled reconfigurations
    # (scale_out / scale_in / retune advisories); "advise" = the same
    # loop evaluates and journals every decision but applies none
    # (dry-run — the counters and the journal audit trail still fill).
    autoscale: Optional[str] = None
    # Latency SLOs the autoscaler's PREDICTIONS are held to, seconds.
    # slo_ttft_s governs time-to-first-token p99 — admission wait on
    # the ROUTED pool plus the prefill pass; slo_tpot_s governs
    # time-per-output-token p99 — the decode-step interval on whichever
    # pool decodes. At least one must be set when autoscale is on
    # (a policy with no objective can never act). Both are PREDICTED
    # quantities over the fitted traffic profile, distinct from
    # slo_queue_delay_s, which is the router's MEASURED admission gate.
    slo_ttft_s: Optional[float] = None
    slo_tpot_s: Optional[float] = None
    # Minimum cluster steps between APPLIED autoscale actions — the
    # hysteresis floor that keeps a burst from triggering a scale_out /
    # scale_in flap (counted in cluster steps, never wall clock, so
    # replays reproduce decisions).
    autoscale_cooldown_steps: int = 64
    # The replica-count band the policy may move within. max_replicas
    # must be set (>= min) when autoscale="drive" — an unbounded
    # scale_out is a cost bug, not a default.
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 0

    def validate_cluster(self, *, specinfer: bool = False) -> None:
        """Fail-fast validation of the cluster fields — called from
        engine construction (every replica carries this config, so a
        bad value dies before any replica exists) AND from
        ClusterManager, the consumer (cluster/manager.py), mirroring
        how ``kv_quant``/``fused_decode`` fail at construction rather
        than mid-serve. ``specinfer=True`` (LLM.compile with ssms)
        additionally rejects SpecInfer × DISAGGREGATED pools — the
        prefill→decode migration itself (including its RPC wire
        transport) is built; what it does not carry yet is the SSM
        mirror engines' draft caches. Plain replicated clusters
        compose (per-replica SSM mirror engines,
        serve/cluster/replica.py)."""
        if specinfer and self.prefill_replicas:
            raise ValueError(
                "disaggregated prefill/decode pools are not composed "
                "with SpecInfer ssms — the prefill→decode migration "
                "hand-off (built, including the multiplexed RPC wire "
                "transport, serve/cluster/remote.py) ships only the "
                "TARGET engine's pages; the remaining gap is shipping "
                "the draft mirrors' caches in the same hand-off. Use "
                "replicas > 1 WITHOUT prefill_replicas/decode_replicas "
                "(each replica then runs its own SSM mirrors, "
                "serve/cluster/replica.py)"
            )
        if self.replicas < 1:
            raise ValueError(
                f"replicas must be >= 1 (got {self.replicas})"
            )
        if self.router_policy not in ("prefix", "round_robin",
                                      "least_loaded"):
            raise ValueError(
                f"unknown router_policy {self.router_policy!r} (expected "
                "'prefix', 'round_robin' or 'least_loaded')"
            )
        if (self.prefill_replicas < 0) or (self.decode_replicas < 0):
            raise ValueError("prefill_replicas/decode_replicas must be >= 0")
        if bool(self.prefill_replicas) != bool(self.decode_replicas):
            raise ValueError(
                "disaggregated serving needs BOTH pools: set "
                "prefill_replicas and decode_replicas together (got "
                f"prefill={self.prefill_replicas}, "
                f"decode={self.decode_replicas})"
            )
        if self.prefill_replicas:
            if self.prefill_replicas + self.decode_replicas != self.replicas:
                raise ValueError(
                    f"prefill_replicas ({self.prefill_replicas}) + "
                    f"decode_replicas ({self.decode_replicas}) must equal "
                    f"replicas ({self.replicas})"
                )
            if self.kv_layout != "paged":
                raise ValueError(
                    "disaggregated prefill/decode pools require "
                    "kv_layout='paged' — prefill→decode migration ships "
                    "KV PAGES (gather_page_kv/scatter_page_kv), which "
                    "the dense layout does not have"
                )
        if self.slo_queue_delay_s is not None and self.slo_queue_delay_s < 0:
            raise ValueError(
                f"slo_queue_delay_s must be >= 0 (got "
                f"{self.slo_queue_delay_s})"
            )
        if self.slo_queue_delay_s is not None and self.prefill_replicas:
            # Under disaggregated pools the ROUTED set is the PREFILL
            # pool only (cluster/manager.py rebuild_routing), so this
            # SLO would shed on prefill-pool admission delay while the
            # decode pool's backlog — where TPOT pain actually lives —
            # stays invisible to admission. That half-blind gate has
            # bitten quietly; refuse it loudly instead.
            raise ValueError(
                "slo_queue_delay_s is not composed with disaggregated "
                "prefill/decode pools: the router only sees the PREFILL "
                "pool's queue-delay estimates (routing targets the "
                "prefill pool; decode backlog is invisible to "
                "admission), so the SLO would govern only prefill "
                "admission wait and silently ignore decode saturation. "
                "Use slo_ttft_s/slo_tpot_s with autoscale to manage a "
                "disaggregated cluster's latency, or drop the pools "
                f"(got slo_queue_delay_s={self.slo_queue_delay_s}, "
                f"prefill_replicas={self.prefill_replicas})"
            )
        if self.failover_retries < 0:
            raise ValueError(
                f"failover_retries must be >= 0 (got "
                f"{self.failover_retries})"
            )
        if self.failover_backoff_steps < 1:
            raise ValueError(
                f"failover_backoff_steps must be >= 1 (got "
                f"{self.failover_backoff_steps})"
            )
        if (
            self.migration_queue_budget is not None
            and self.migration_queue_budget < 0
        ):
            raise ValueError(
                f"migration_queue_budget must be >= 0 or None (got "
                f"{self.migration_queue_budget})"
            )
        if self.replica_transport not in ("inproc", "loopback", "socket"):
            raise ValueError(
                f"unknown replica_transport {self.replica_transport!r} "
                "(expected 'inproc', 'loopback' or 'socket')"
            )
        if self.standby_replicas < 0:
            raise ValueError(
                f"standby_replicas must be >= 0 (got "
                f"{self.standby_replicas})"
            )
        if self.standby_replicas and self.prefill_replicas:
            raise ValueError(
                "warm standbys are not composed with disaggregated "
                "prefill/decode pools yet — a standby adopts ONE routing "
                "position, which is ambiguous across split pools; use "
                "standby_replicas with mixed replicas"
            )
        if self.replica_transport == "socket":
            want = self.replicas + self.standby_replicas
            if len(self.replica_endpoints) != want:
                raise ValueError(
                    "replica_transport='socket' needs one "
                    "replica_endpoints entry per replica + standby "
                    f"(want {want}, got {len(self.replica_endpoints)})"
                )
        if self.rpc_deadline_s <= 0:
            raise ValueError(
                f"rpc_deadline_s must be > 0 (got {self.rpc_deadline_s})"
            )
        if self.rpc_retries < 0:
            raise ValueError(
                f"rpc_retries must be >= 0 (got {self.rpc_retries})"
            )
        if self.rpc_backoff_s < 0:
            raise ValueError(
                f"rpc_backoff_s must be >= 0 (got {self.rpc_backoff_s})"
            )
        if self.heartbeat_interval_steps < 1:
            raise ValueError(
                f"heartbeat_interval_steps must be >= 1 (got "
                f"{self.heartbeat_interval_steps})"
            )
        if self.heartbeat_gap_steps < 1:
            raise ValueError(
                f"heartbeat_gap_steps must be >= 1 (got "
                f"{self.heartbeat_gap_steps})"
            )
        if self.journal_dir is not None and not str(self.journal_dir):
            raise ValueError(
                "journal_dir must be a non-empty directory path or None"
            )
        if self.autoscale not in (None, "drive", "advise"):
            raise ValueError(
                f"unknown autoscale {self.autoscale!r} (expected None, "
                "'drive' or 'advise')"
            )
        if self.slo_ttft_s is not None and self.slo_ttft_s <= 0:
            raise ValueError(
                f"slo_ttft_s must be > 0 (got {self.slo_ttft_s})"
            )
        if self.slo_tpot_s is not None and self.slo_tpot_s <= 0:
            raise ValueError(
                f"slo_tpot_s must be > 0 (got {self.slo_tpot_s})"
            )
        if self.autoscale_cooldown_steps < 1:
            raise ValueError(
                f"autoscale_cooldown_steps must be >= 1 (got "
                f"{self.autoscale_cooldown_steps})"
            )
        if self.autoscale_min_replicas < 1:
            raise ValueError(
                f"autoscale_min_replicas must be >= 1 (got "
                f"{self.autoscale_min_replicas})"
            )
        if self.autoscale is not None:
            if self.slo_ttft_s is None and self.slo_tpot_s is None:
                raise ValueError(
                    f"autoscale={self.autoscale!r} needs an objective: "
                    "set slo_ttft_s and/or slo_tpot_s (PREDICTED-latency "
                    "SLOs — the policy scales to hold them)"
                )
            if self.autoscale_max_replicas < self.autoscale_min_replicas:
                raise ValueError(
                    f"autoscale_max_replicas "
                    f"({self.autoscale_max_replicas}) must be >= "
                    f"autoscale_min_replicas "
                    f"({self.autoscale_min_replicas}) when autoscale is "
                    "on — an unbounded scale_out is a cost bug, so the "
                    "ceiling is explicit"
                )
            if not (
                self.autoscale_min_replicas <= self.replicas
                <= self.autoscale_max_replicas
            ):
                raise ValueError(
                    f"replicas ({self.replicas}) must start inside the "
                    f"autoscale band [{self.autoscale_min_replicas}, "
                    f"{self.autoscale_max_replicas}]"
                )

    def resolved_context_shards(self, mesh_seq_degree: int = 1) -> int:
        """The context-parallel degree this config resolves to on a mesh
        with ``mesh_seq_degree`` sequence shards (1 when kv_shard is
        off)."""
        if self.kv_shard != "context":
            return 1
        return self.context_shards or max(1, int(mesh_seq_degree))

    def validate_long_context(self, *, mesh_seq_degree: int = 1) -> None:
        """Fail-fast validation of the context-parallel fields — called
        from engine construction (like :meth:`validate_cluster`), so a
        bad combination dies before any pool is allocated, naming the
        fix instead of failing mid-serve."""
        if self.kv_shard not in ("none", "context"):
            raise ValueError(
                f"unknown kv_shard {self.kv_shard!r} (expected 'none' "
                "or 'context')"
            )
        if self.context_shards < 0:
            raise ValueError(
                f"context_shards must be >= 0 (got {self.context_shards})"
            )
        if self.kv_shard == "none":
            if self.context_shards > 1:
                raise ValueError(
                    f"context_shards={self.context_shards} has no effect "
                    "without kv_shard='context' — set kv_shard, or drop "
                    "context_shards"
                )
            return
        if self.kv_layout != "paged":
            raise ValueError(
                "kv_shard='context' requires kv_layout='paged' — context "
                "parallelism shards KV PAGES across sequence shards, "
                "which the dense per-slot layout does not have"
            )
        n = self.resolved_context_shards(mesh_seq_degree)
        if n < 2:
            raise ValueError(
                "kv_shard='context' needs at least 2 shards: set "
                f"context_shards >= 2 (got {self.context_shards}) or "
                "serve on a mesh with a seq-axis degree > 1 "
                f"(mesh seq degree is {mesh_seq_degree})"
            )
        if mesh_seq_degree > 1 and n != mesh_seq_degree:
            raise ValueError(
                f"context_shards ({n}) must equal the mesh seq-axis "
                f"degree ({mesh_seq_degree}) when the mesh is sequence-"
                "sharded — each shard owns one slice of the pool; set "
                "context_shards=0 to derive the degree from the mesh"
            )
        if (
            self.max_cached_tokens is not None
            and self.max_cached_tokens < self.page_size
        ):
            raise ValueError(
                f"kv_shard='context' prices max_cached_tokens "
                f"({self.max_cached_tokens}) PER SHARD, and each shard "
                f"needs at least one whole page (page_size="
                f"{self.page_size}) — raise the budget or shrink "
                "page_size"
            )
        # PR-11's blanket rope_kv_write exclusion on sequence-sharded
        # meshes is LIFTED: the fused prologue now joins the ring body
        # (serve/kernels.ring_ragged_paged_attention fused mode — each
        # shard rotates Q/K and commits its resident lines inside the
        # shard_map program). What remains excluded is the QUANTIZED
        # ring commit: the per-page amax scale update is not
        # shard-local.
        if (
            "rope_kv_write" in (self.fused_decode or ())
            and mesh_seq_degree > 1
            and self.kv_quant is not None
        ):
            raise ValueError(
                "fused_decode='rope_kv_write' is not composed with "
                "QUANTIZED pools on a sequence-sharded mesh — the "
                "in-ring quantizing commit's per-page scale update is "
                "not shard-local; drop kv_quant or the fusion (full-"
                "precision pools compose)"
            )

    @property
    def cache_len(self) -> int:
        # Committed tokens + in-flight speculative tree slack
        # (reference BatchConfig::MAX_SPEC_TREE_TOKEN_NUM headroom).
        return self.max_sequence_length + self.max_spec_tree_tokens

    @property
    def mixed_chunk(self) -> int:
        """Static per-row chunk width of the mixed continuous-batching
        step (its compiled token-matrix is (slots, mixed_chunk)) — the
        per-slot per-step prefill token budget."""
        if self.max_tokens_per_step <= 0:
            return self.prefill_chunk
        return max(1, min(self.prefill_chunk, self.max_tokens_per_step))

    @property
    def pages_per_slot(self) -> int:
        """Logical pages covering one slot's worst case (cache_len lines
        + the scratch line)."""
        return -(-(self.cache_len + 1) // self.page_size)

    @property
    def num_pages(self) -> int:
        """Physical pages in the pool (excluding the scratch page).
        Under ``kv_shard='context'`` this is the PER-SHARD page count
        (``max_cached_tokens`` is a per-shard HBM budget); the engine
        sizes the total pool at ``num_pages × context_shards``."""
        if self.max_cached_tokens is None:
            return self.max_requests_per_batch * self.pages_per_slot
        return max(
            self.pages_per_slot if self.kv_shard != "context" else 1,
            -(-self.max_cached_tokens // self.page_size),
        )


@functools.lru_cache(maxsize=None)
def pack_widths(slots: int, chunk: int) -> Tuple[int, ...]:
    """The widths a (slots, chunk) mixed step's token axis is compiled
    at, ascending: a quarter, a half and the whole of ``slots * chunk``
    (the whole is the padded step: what fits there fits today), less
    any rung narrower than one chunk, and under them the ADMISSION
    rung: the places of a step in which every slot decodes but the one
    whose prompt's chunk rides along, ``slots + chunk`` rounded up to a
    power of two, where that is narrower than the quarter rung (64 x
    128: 256 under 2048, 4096, 8192; 16 x 128: 256 under 512; 4 x 128:
    none, its quarter is 128). From the two extents alone; a step
    takes the narrowest rung that holds its real tokens
    (:meth:`InferenceEngine.run_mixed`). Few rungs because each is a
    program traced and lowered in set-up (with a warm compile cache
    0.4-1.2 s of host time a rung at 16 slots, 2.5-4.6 s for the
    64-slot families' longer programs, PERF.md section 6), by halves from the
    top because a closed loop of prompts fills about a quarter of a
    mixed step, one at the bottom because a closed loop of decoding
    rows fills slots + chunk whatever the slots: a fiftieth of 64 x
    128. One rung is no ladder: ``chunk == 1``, a single slot. The
    scheduler keeps a step under a rung: where the decoding rows' own
    tokens push whole chunks a few places over one, the newest prompt's
    chunk gives them up (``request_manager.trim_to_rung``)."""
    top = slots * chunk
    if chunk == 1:
        return (top,)
    rungs = tuple(w for w in (-(-top // 4), -(-top // 2), top) if w >= chunk)
    admission = 1 << (slots + chunk - 1).bit_length()
    return (admission,) + rungs if admission < rungs[0] else rungs


def _abstract(tree):
    """The abstract twin of a tree of tracers (or arrays): what
    ``jit(...).lower`` takes in their place. Shape, dtype, weak type
    and the sharding the type carries are the whole of the tracing
    cache's key, so lowering with it traces nothing again."""
    def leaf(x):
        aval = jax.typeof(x)
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                    sharding=aval.sharding,
                                    weak_type=aval.weak_type)
    return jax.tree.map(leaf, tree)


def program_name(key: Any) -> str:
    """The stable name of the program compiled under the step key
    ``key`` (:meth:`InferenceEngine._jit`): what JAX calls its module
    (``jit_<name>``) in lowered HLO and on a profile's ``XLA Modules``
    line. Every per-step program starts ``ff_step_``; ``c<n>`` is the
    chunk — ``ff_step_c1`` the pipelined decode step, ``ff_step_c128``
    the pipelined mixed step at ``mixed_chunk=128``, ``ff_step_c128_t512``
    that step with its token axis packed at 512 places and
    ``ff_step_c128_t256`` at the admission rung's 256
    (:func:`pack_widths`). Those are the programs with the argmax head,
    the one an all-greedy batch takes (serve/sampling.py): the greedy
    head is the unmarked one, and a program whose head samples carries
    the suffix that names its extra work, ``_sample``, ``_topk<cap>``
    or ``_full`` (``ff_step_c1_topk8``)."""
    if isinstance(key, str):  # commit, copy_page, reorder, ...
        return f"ff_{key}"

    def flags(**on):
        return "".join(f"_{word}" for word, yes in on.items() if yes)

    def head(mode, cap):  # the sampling head the step compiled in
        return "" if mode == "greedy" else f"_{mode}{cap or ''}"

    kind, *rest = key
    if kind == "mixed_packed":  # returns its logits to every caller
        chunk, width, mode, cap = rest
        return f"ff_step_c{chunk}_t{width}" + head(mode, cap)
    if kind == "mixed_fused":
        chunk, with_logits, mode, cap = rest
        return (f"ff_step_c{chunk}" + flags(logits=with_logits)
                + head(mode, cap))
    if kind == "step_sampled":
        chunk, with_mask, mode, cap, with_logits = rest
        return (f"ff_step_sampled_c{chunk}"
                + flags(logits=with_logits, mask=with_mask) + head(mode, cap))
    if kind == "speculate":
        return "ff_speculate_" + "_".join(str(p) for p in rest)
    chunk, all_logits, with_mask = key  # the two-dispatch sync step (_get_step)
    return f"ff_step_sync_c{chunk}" + flags(logits=all_logits, mask=with_mask)


class InferenceEngine:
    """Owns device-resident params + KV cache and the jitted step fns.

    ``model`` is a model-family module exposing the serving protocol
    (see models/transformer.py): ``init_kv_cache(cfg, slots, max_len, dtype)``,
    ``commit_kv(cache, src, dst)`` and
    ``serve_step(params, cache, tokens, positions, logits_idx, mask,
    cache_positions, *, cfg, all_logits)``.
    """

    def __init__(
        self,
        model: Any,
        cfg: Any,
        params: Dict[str, Any],
        serving: Optional[ServingConfig] = None,
        mesh: Optional[Mesh] = None,
    ):
        import os

        self.model = model
        self.cfg = cfg
        self.serving = serving or ServingConfig()
        if self.serving.inference_debugging is None:
            self.serving = dataclasses.replace(
                self.serving,
                inference_debugging=os.environ.get("FF_INFERENCE_DEBUGGING")
                or None,
            )
        self._debug_step = 0
        self.mesh = mesh or MachineSpec().make_mesh(jax.devices()[:1])
        self.params = params
        # Key: (chunk, all_logits, with_mask) for plain steps, or a
        # tagged tuple for the steps that sample on the device
        # (("mixed_fused", chunk, with_logits, mode, cap);
        # ("mixed_packed", chunk, width, mode, cap) a rung of its
        # ladder; mode and cap name the head, serve/sampling.py).
        self._steps: Dict[Any, Callable] = {}
        # program name -> (jitted, abstract arguments), kept by _jit's
        # wrapper as it is traced (step_program_texts)
        self._traced: Dict[str, Tuple[Callable, Any]] = {}
        sublayers.register(self)
        # what each program's build cost, by name (obs/builds.py): the
        # process's one set of jax.monitoring listeners is registered
        # here, at the first engine's construction
        self.build_log = BuildLog()
        # serving ladders (chunk, sampling head) whose every rung is
        # compiled
        self._ladders_compiled: set = set()
        self._commit: Optional[Callable] = None
        # Hazard sanitizers (flexflow_tpu/analysis — see
        # ServingConfig.sanitizers): every step program is created
        # through self._jit, which the RetraceGuard hooks; every donated
        # dispatch hands the old cache to self._poison_donated.
        self.retrace_guard = None
        self.donation_sanitizer = None
        self.lock_sanitizer = None
        sanitizers = self.serving.sanitizers
        if isinstance(sanitizers, str):
            sanitizers = tuple(
                s.strip() for s in sanitizers.split(",") if s.strip()
            )
        if not sanitizers:
            env = os.environ.get("FF_SANITIZERS", "")
            sanitizers = tuple(s.strip() for s in env.split(",") if s.strip())
        for name in sanitizers:
            if name in ("retrace", "retrace-warn"):
                from ..analysis.retrace import RetraceGuard

                self.retrace_guard = RetraceGuard(strict=(name == "retrace"))
            elif name == "donation":
                from ..analysis.donation import DonationSanitizer

                self.donation_sanitizer = DonationSanitizer()
            elif name == "locks":
                from ..analysis.locks import enable_lock_sanitizer

                # process-global (locks are shared across engines in a
                # loopback cluster); idempotent — a second engine joins
                # the already-active sanitizer
                self.lock_sanitizer = enable_lock_sanitizer(strict=True)
            else:
                raise ValueError(
                    f"unknown sanitizer {name!r} (expected 'retrace', "
                    "'retrace-warn', 'donation' or 'locks')"
                )
        # Cluster fields (serve/cluster/) fail here, at the first
        # replica's engine construction, like kv_quant/fused_decode do.
        self.serving.validate_cluster()
        self.paged = self.serving.kv_layout == "paged"
        if self.serving.kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"unknown kv_layout {self.serving.kv_layout!r} "
                "(expected 'dense' or 'paged')"
            )
        if not self.paged and self.serving.kernels != "xla":
            raise ValueError(
                f"kernels={self.serving.kernels!r} requires "
                "kv_layout='paged': the dense layout is the XLA reference "
                "layout and has no Pallas kernels"
            )
        # Context-parallel long-context serving (kv_shard="context"):
        # resolve the shard degree against this engine's mesh and fail
        # bad combinations here, not mid-serve.
        from ..core.mesh import SEQ_AXIS

        seq_deg = self.mesh.shape.get(SEQ_AXIS, 1)
        self.serving.validate_long_context(mesh_seq_degree=seq_deg)
        self.cp_shards = self.serving.resolved_context_shards(seq_deg)
        # per-shard BUDGET pages (quant-converted) the admission check
        # enforces; set by _alloc_cache when max_cached_tokens is given
        self.cp_budget_pages_per_shard = None
        # the ring shard_map program only engages on a mesh that is
        # actually sequence-sharded; on a seq-degree-1 mesh every shard
        # is locally addressable and the plain table gather IS the ring
        # result (bitwise the CP-off step — serve/kernels.py)
        self.cp_ring = self.cp_shards > 1 and seq_deg > 1
        # Decode-step fusions: validate the set up front so a bad
        # toggle fails at engine construction, not mid-serve.
        fused = self.serving.fused_decode
        if isinstance(fused, str):
            fused = tuple(s.strip() for s in fused.split(",") if s.strip())
            self.serving = dataclasses.replace(self.serving,
                                               fused_decode=fused)
        for name in fused:
            if name != "rope_kv_write":
                raise ValueError(
                    f"unknown fused_decode entry {name!r} (expected "
                    "'rope_kv_write')"
                )
        if "rope_kv_write" in fused:
            if not self.paged:
                raise ValueError(
                    "fused_decode='rope_kv_write' requires "
                    "kv_layout='paged' — the fused prologue commits K/V "
                    "through the page table inside the ragged paged "
                    "kernel"
                )
            if "rope_kv_write" not in getattr(model, "FUSED_DECODE", ()):
                raise ValueError(
                    "fused_decode='rope_kv_write' requested but "
                    f"{getattr(model, '__name__', repr(model))} does not "
                    "advertise it (model.FUSED_DECODE) — the family's "
                    "serve_step_paged has no fused prologue"
                )
        # Dispatch telemetry: device programs this
        # engine's serving loop issued — every jitted step dispatched
        # here plus host-side decode heads the scheduler counts via
        # count_dispatch (a step that samples on the device is one
        # program, the two-dispatch sync step two).
        self.dispatch_count = 0
        # the sampling head (mode, topk_cap) the newest mixed / decode
        # step was dispatched with: what its batch's decode-head arrays
        # chose (run_mixed); the scheduler counts its steps by it
        self.step_head: Tuple[str, int] = ("greedy", 0)
        # A family whose step returns counters of its own beside its
        # cache (``step_counts(cfg)``: name -> shape of int32 entries
        # of the cache a step returns that are no state, a sparse
        # model's tokens per expert) has them packed behind the sampled
        # tokens in ONE int32 array, ``step_fetch`` (the newest mixed
        # step's, a device array): the scheduler's flush fetches that
        # one array where it would fetch the tokens (split_fetch)
        # (the paged step's alone: the dense layout returns none)
        self._step_counts = (
            getattr(model, "step_counts", lambda cfg: {})(cfg)
            if self.paged else {})
        self.step_fetch = None
        # ... and the row tile of that step's grouped expert matmuls
        # (models/transformer.py ``routed_tile`` at the width the step
        # ran and what the family's ``expert_routing(cfg)`` declares
        # beside its ``step_counts``), under which the scheduler counts
        # the tiles its tokens per expert fill
        self.step_tile = 0
        self._expert_routing = (
            getattr(model, "expert_routing")(cfg) if self._step_counts else ())
        # Observability (flexflow_tpu/obs): count_dispatch doubles as
        # the tracing chokepoint — with a tracer attached (shared with
        # the owning scheduler's lane by obs.attach_observability),
        # every dispatched device program becomes a trace event, which
        # is what lets a timeline show dispatched-programs-per-step.
        # NULL_TRACER (default) keeps the counter a bare increment.
        self.tracer = NULL_TRACER
        # Quantized KV pages (serve/kv_quant.py): validated up front so
        # a bad value fails at engine construction, not mid-serve.
        self.kv_quant_spec = None
        if self.serving.kv_quant is not None:
            if not self.paged:
                raise ValueError(
                    "kv_quant requires kv_layout='paged' — the dense "
                    "layout has no per-page scale granularity"
                )
            from .kv_quant import resolve_spec

            self.kv_quant_spec = resolve_spec(self.serving.kv_quant)
        if self.serving.cache_policy not in ("complete", "prefill"):
            raise ValueError(
                f"unknown cache_policy {self.serving.cache_policy!r} "
                "(expected 'complete' or 'prefill')"
            )
        # Hierarchical KV host tier: validated up front — the spill
        # path only exists as the prefix cache's eviction alternative.
        if self.serving.host_cache_bytes:
            if not self.paged or not self.serving.prefix_caching:
                raise ValueError(
                    "host_cache_bytes requires kv_layout='paged' with "
                    "prefix_caching=True — the host tier spills cold "
                    "prefix-cache pages, so there is nothing to spill "
                    "without the radix tree"
                )
        self.pager = None  # PageAllocator when paged (host-side tables)
        if self.pipelined:
            pp = self.mesh.shape["pipe"]
            L = cfg.num_hidden_layers
            if L % pp:
                raise ValueError(
                    f"pipeline serving needs num_hidden_layers ({L}) "
                    f"divisible by the pipe degree ({pp})"
                )
            if self.paged:
                raise ValueError(
                    "kv_layout='paged' is not composed with pipeline "
                    "parallelism yet — use kv_layout='dense' with pipe>1"
                )
        # A family whose cache holds more than pages (per-slot recurrent
        # state) says here which of the options above it cannot serve yet
        validate = getattr(model, "validate_serving", None)
        if validate is not None:
            validate(cfg, self.serving, self.mesh)
        self.cache = self._alloc_cache()

    @property
    def pipelined(self) -> bool:
        """Serve-time pipeline parallelism: stage-sharded layer stack
        (reference inference_manager.cc:91-133 stage assignment)."""
        from ..core.mesh import PIPE_AXIS

        return self.mesh.shape.get(PIPE_AXIS, 1) > 1

    def _alloc_cache(self):
        """Allocate the KV cache sharded over the mesh (the model's
        kv_cache_pspecs: slots — or pages, when paged — on the data
        axis, KV heads on the model axis) — the analog of the
        reference's per-shard tensor_buffer allocation
        (inference_manager.cc:143-200). The paged branch also (re)builds
        the host-side page allocator: a fresh cache means empty tables."""
        sc = self.serving
        if self.paged:
            from .paging import PageAllocator

            num_pages = sc.num_pages
            if self.kv_quant_spec is not None and sc.max_cached_tokens is not None:
                # bytes-per-page accounting (serve/kv_quant.py): the
                # max_cached_tokens budget is an HBM budget priced at
                # cache_dtype — int8 pages cost ~half the bytes, so the
                # same budget exposes ~2x the pages to the allocator
                from .kv_quant import quantized_pool_pages

                num_pages = quantized_pool_pages(
                    num_pages,
                    sc.page_size,
                    self.cfg.num_key_value_heads,
                    self.cfg.head_dim,
                    jnp.dtype(sc.cache_dtype).itemsize,
                    self.kv_quant_spec,
                )
            extra_rows = 0
            if self.cp_shards > 1:
                # context parallelism: num_pages is the PER-SHARD
                # budget; the pool holds every shard's slice. Like the
                # single-pool layout (whose num_pages property clamps
                # up to pages_per_slot), the ALLOCATOR is clamped to
                # one slot's striped worst case so construction always
                # succeeds — the admission check enforces the BUDGET
                # (request_manager reads cp_budget_pages_per_shard, so
                # an over-budget prompt is a terminal ERROR, the PR-2
                # live-lock contract, never a constructor crash).
                self.cp_budget_pages_per_shard = (
                    num_pages if sc.max_cached_tokens is not None else None
                )
                per_shard = max(
                    num_pages, -(-sc.pages_per_slot // self.cp_shards)
                )
                num_pages = per_shard * self.cp_shards
                # The ring layout shards pool ROWS over the seq axis:
                # pad with unreferenced rows until (total + scratch)
                # divides the degree — the allocator never hands a pad
                # row out (its num_pages excludes them) and the scratch
                # row keeps index num_pages.
                if self.cp_ring:
                    extra_rows = (-(num_pages + 1)) % self.cp_shards
            else:
                data = self.mesh.shape.get(DATA_AXIS, 1)
                if data > 1:
                    # pool rows (num_pages + scratch) shard over data —
                    # round up so the leading dim divides evenly
                    num_pages += (-(num_pages + 1)) % data
            init_kw = dict(kv_quant=sc.kv_quant)
            classes = self._page_classes()
            if classes is None:
                self.pager = PageAllocator(
                    num_pages, sc.pages_per_slot, sc.max_requests_per_batch,
                    sc.page_size, cp_shards=self.cp_shards,
                )
            else:
                self.pager = self._class_pagers(classes, num_pages)
                # a pool a class: the family sizes each by ``class_pages``
                # (the positional count is its first class's)
                num_pages = next(iter(self.pager.classes.values())).num_pages
                init_kw["class_pages"] = {
                    name: a.num_pages
                    for name, a in self.pager.classes.items()}
            self._table_cache = None  # fresh pager → stale device copy
            if extra_rows:
                init_kw["extra_rows"] = extra_rows
            if getattr(self.model, "SLOT_STATE", ()):
                # per-slot state beside the pool (a recurrent state, a
                # per-position index): the family sizes it by slots and
                # by the longest context a slot may hold
                init_kw.update(num_slots=sc.max_requests_per_batch,
                               cache_len=sc.cache_len)
            init = functools.partial(
                self.model.init_paged_kv_cache,
                self.cfg,
                num_pages,
                sc.page_size,
                sc.cache_dtype,
                **init_kw,
            )
            pspec_fn = functools.partial(
                self.model.paged_kv_cache_pspecs, kv_quant=sc.kv_quant,
                kv_shard=sc.kv_shard if self.cp_ring else None,
            )
        else:
            init = functools.partial(
                self.model.init_kv_cache,
                self.cfg,
                sc.max_requests_per_batch,
                sc.cache_len,
                sc.cache_dtype,
            )
            pspec_fn = self.model.kv_cache_pspecs
        with _set_mesh(self.mesh):
            if any(n > 1 for n in self.mesh.shape.values()):
                pspecs = pspec_fn(self.cfg, pipeline=self.pipelined)
                shardings = jax.tree.map(
                    lambda p: NamedSharding(self.mesh, p),
                    pspecs,
                    is_leaf=lambda x: isinstance(x, P),
                )
                return jax.jit(init, out_shardings=shardings)()
            return init()

    def _page_classes(self):
        """The family's classes of page (``page_classes(cfg)`` beside
        its ``PAGE_POOLS``): name -> (the class's pools, its window or
        None); None where it declares none, and everything is as it
        was: one allocator, one table, one class of page."""
        declared = getattr(self.model, "page_classes", None)
        return None if declared is None else declared(self.cfg)

    def _class_pagers(self, classes, num_pages: int):
        """One allocator a class (serve/paging.PageClasses). A class
        with a window takes its worst case, a rolling table a slot
        (``window_table_pages``: no request can hold more), and the
        others share what is left of ``num_pages``, the whole budget
        counted in pages of any class, never under one slot's whole
        context. A class whose window is no shorter than the context
        keeps every page like the others."""
        from .paging import PageAllocator, PageClasses, window_table_pages

        sc = self.serving
        slots, ps = sc.max_requests_per_batch, sc.page_size
        step_lines = max(sc.mixed_chunk, sc.prefill_chunk)
        rolling = {}
        for name, (_, window) in classes.items():
            if window is not None:
                per = window_table_pages(window, step_lines, ps)
                if per < sc.pages_per_slot:
                    rolling[name] = per
        whole = [name for name in classes if name not in rolling]
        left = num_pages - slots * sum(rolling.values())
        if sc.max_cached_tokens is None:
            left = slots * sc.pages_per_slot * len(whole)
        pagers = {}
        for name, (_, window) in classes.items():
            if name in rolling:
                pagers[name] = PageAllocator(
                    slots * rolling[name], rolling[name], slots, ps,
                    window=window, step_lines=step_lines)
            else:
                pagers[name] = PageAllocator(
                    max(left // len(whole), sc.pages_per_slot),
                    sc.pages_per_slot, slots, ps)
        return PageClasses(pagers)

    # ------------------------------------------------------------------
    # paged-layout accounting (bench + tests)

    def page_table_device(self):
        """The engine's own page table as a device array — every step's
        read-only gather/scatter indices (with several classes of page
        a dict of them, ``PageClasses.tables``). Cached against the
        allocator's version counter: steady-state decode (no
        admissions, no page growth) re-ships nothing."""
        cached = getattr(self, "_table_cache", None)
        if cached is not None and cached[0] == self.pager.version:
            return cached[1]
        dev = jax.tree.map(lambda t: jnp.asarray(t, dtype=jnp.int32),
                           self.pager.tables())
        self._table_cache = (self.pager.version, dev)
        return dev

    def kv_cache_bytes(self) -> int:
        """Device bytes held by the cache buffers (dense: the whole
        slots × max_len cache; paged: the page pool)."""
        return sum(int(leaf.nbytes) for leaf in jax.tree.leaves(self.cache))

    def kv_bytes_per_line(self) -> float:
        """Bytes one cached token line costs across all layers, counted
        from the family's own pool arrays (its ``PAGE_POOLS``; K and V
        and their scale rows where it names none: a latent pool holds
        one compressed line and no K/V heads) — quantized pools
        amortize their per-page f32 scale rows into the per-line
        figure, so the metric stays an honest HBM cost."""
        classes = self._page_classes() if self.paged else None
        if classes is not None:  # a line is held once in every class
            return sum(self._class_bytes_per_line(pools)
                       for pools, _ in classes.values())
        return self._class_bytes_per_line(getattr(
            self.model, "PAGE_POOLS", ("k", "v", "k_scale", "v_scale")))

    def _class_bytes_per_line(self, names) -> float:
        pools = [self.cache[name] for name in names if name in self.cache]
        # slots×(len+1) or pages×page_size
        lines = pools[0].shape[1] * pools[0].shape[2]
        return sum(int(a.nbytes) for a in pools) / lines

    def slot_state_bytes(self) -> int:
        """Bytes of the cache held per SLOT and not per page (a family's
        ``SLOT_STATE`` entries: recurrent states, compressed keys):
        constant in the context length, held whether a slot is in use
        or not. 0 for a family whose cache is pages alone."""
        return sum(int(self.cache[name].nbytes)
                   for name in getattr(self.model, "SLOT_STATE", ()))

    def kv_allocated_bytes(self) -> int:
        """Bytes of KV HBM backing ALLOCATED pages (paged layout): the
        footprint proportional-to-live-tokens claim, measured. Per-slot
        state counts whole: it is held at any length."""
        if not self.paged:
            return self.kv_cache_bytes()
        classes = self._page_classes()
        if classes is not None:
            used = sum(
                self.pager.classes[name].used_pages
                * self._class_bytes_per_line(pools)
                for name, (pools, _) in classes.items())
        else:
            used = self.pager.used_pages * self.kv_bytes_per_line()
        return int(used * self.serving.page_size) + self.slot_state_bytes()

    @property
    def scratch_pos(self) -> int:
        return self.serving.cache_len

    @property
    def num_slots(self) -> int:
        return self.serving.max_requests_per_batch

    # ------------------------------------------------------------------
    # sanitizer chokepoints (flexflow_tpu/analysis)

    def _jit(self, fn: Callable, *, key: Any,
             donate_argnums: Tuple[int, ...] = ()) -> Callable:
        """Every step program (``_steps``/``_commit``) is compiled
        through this chokepoint, which does three things. It NAMES the
        program from its key (:func:`program_name`), so a profile's
        ``XLA Modules`` line and the lowered HLO read
        ``jit_ff_step_c1`` / ``jit_ff_step_c128`` instead of the name
        of whatever closure was jitted. Its wrapper, which runs when
        the program is traced and never at a dispatch, opens the
        build's record in the engine's build log under that name
        (obs/builds.py: the one count of ``SchedulerStats.compiles`` /
        ``retraces``, the seconds of each part, the ``ff.build.trace``
        annotation round the traced function). And it lets the retrace
        sentinel observe it: the guard wraps the function (keeping its
        name) to record each trace — which is exactly one XLA compile —
        under ``key`` and, in strict mode, raises on any recompile of a
        known key (analysis/retrace.py), before the log has seen it."""

        name = program_name(key)

        @functools.wraps(fn)
        def program(*args, **kwargs):
            # trace time only
            with self.build_log.tracing(name, key, self.tracer):
                # what step_program_texts lowers again
                self._traced[name] = (jitted, _abstract((args, kwargs)))
                return fn(*args, **kwargs)

        program.__name__ = program.__qualname__ = name
        if self.retrace_guard is not None:
            program = self.retrace_guard.instrument(program, key=key)
        jitted = jax.jit(program, donate_argnums=donate_argnums)
        return jitted

    def step_program_texts(self, names=None) -> Dict[str, str]:
        """``{program name: the compiled executable's HLO text}`` of
        the programs this engine has traced through :meth:`_jit` (all,
        or those of ``names``): each is lowered and compiled again with
        the abstract arguments it was traced with, under the engine's
        mesh. The tracing cache answers the first (no trace, so the
        retrace sentinel sees nothing) and the compilation cache the
        second; nothing is dispatched. Seconds a program all the same
        (my chip runs, PR 42: 1-4 s): for ``obs.sublayers.scope_maps``,
        after the measured work, never on the serving path."""
        texts = {}
        with _set_mesh(self.mesh):
            for name, (jitted, (args, kwargs)) in list(self._traced.items()):
                if names is None or name in names:
                    texts[name] = jitted.lower(
                        *args, **kwargs).compile().as_text()
        return texts

    def _carry(self, last_tokens):
        """The sampled-token carry as a step's own output would present
        it. Sharding-in-types puts the array's mesh into its abstract
        type, so a host-built first carry (single-device sharding, empty
        mesh) and a previous step's output (NamedSharding on
        ``self.mesh``) are two tracing-cache keys — every pipelined step
        program would trace and compile twice. Place the host-built one
        on the mesh, replicated, so the first dispatch and the steady
        state present one type."""
        sh = getattr(last_tokens, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh == self.mesh:
            return last_tokens
        # ffcheck: disable=FF107 -- host→device placement of the FIRST carry only (R int32s, asynchronous, nothing is fetched); every later dispatch returns above with the previous step's output
        return jax.device_put(
            jnp.asarray(last_tokens, dtype=jnp.int32),
            NamedSharding(self.mesh, P()),
        )

    def _poison_donated(self, donated: Any, key: Any) -> None:
        """Donation-sanitizer hook: after a donated dispatch the OLD
        cache pytree is poisoned (leaves deleted, entries swapped for
        DeletedBufferProxy) so any lingering host-side reference raises
        UseAfterDonateError at the faulty read instead of silently
        reading donated memory (analysis/donation.py)."""
        if self.donation_sanitizer is not None and donated is not self.cache:
            self.donation_sanitizer.poison(
                donated, context=f"engine step {key!r}"
            )

    # ------------------------------------------------------------------

    def _serve_step_fn(self, all_logits: bool,
                       num_layers: Optional[int] = None,
                       pack: Optional[int] = None,
                       counts: bool = False) -> Callable:
        """model.serve_step (or serve_step_paged) bound to this engine's
        static kwargs. The paged variant takes the page table as a
        trailing positional and needs cache_len for its scratch-line
        mask cutoff. ``num_layers`` binds the LAYER-SLICED early-exit
        draft step (SpecConfig.draft="early_exit"): the model runs only
        its first ``num_layers`` blocks and leaves the deeper cache
        rows untouched. ``pack`` binds a rung of :meth:`pack_ladder`.

        A family that declares ``step_counts`` returns, in the cache of
        its step, entries that are this step's own counters and no
        state: they are taken out of the cache here, so the cache a
        step returns is the cache it was given, and returned third
        where ``counts`` asks for them (a family that declares none
        returns two values either way)."""
        names = tuple(self._step_counts)
        kw = dict(cfg=self.cfg, all_logits=all_logits)
        if num_layers is not None:
            kw["num_layers"] = int(num_layers)
        if pack is not None:
            kw["pack"] = int(pack)
        if self.pipelined:
            kw["mesh"] = self.mesh
        if self.paged:
            kw["cache_len"] = self.serving.cache_len
            if self.serving.kernels != "xla":
                kw["kernels"] = self.serving.kernels
            if self.serving.kv_quant is not None:
                kw["kv_quant"] = self.serving.kv_quant
            if "rope_kv_write" in self.serving.fused_decode:
                kw["fused_rope"] = True
            if self.cp_ring:
                # sequence-sharded pool: attention reads go through the
                # ring ragged paged program (partial shard_map over the
                # seq axis; serve/kernels.ring_ragged_paged_attention)
                kw["cp_mesh"] = self.mesh
            fn = functools.partial(self.model.serve_step_paged, **kw)
            if not names:
                return fn

            def step(*args, **kw):
                logits, cache = fn(*args, **kw)
                cache = dict(cache)
                taken = {name: cache.pop(name) for name in names}
                return (logits, cache, taken) if counts else (logits, cache)

            return step
        return functools.partial(self.model.serve_step, **kw)

    def count_dispatch(self, kind: str = "step") -> None:
        """Record one dispatched device program (see dispatch_count)."""
        self.dispatch_count += 1
        tr = self.tracer
        if tr.enabled:
            tr.event("dispatch", kind=kind)

    def _get_step(self, chunk: int, all_logits: bool, with_mask: bool):
        """One compiled program per static signature — the analog of the
        reference's per-InferenceMode compiled graphs (compile_inference),
        cached like Legion's replayed traces."""
        key = (chunk, all_logits, with_mask)
        if key not in self._steps:
            fn = self._serve_step_fn(all_logits)

            if self.paged:
                def step(params, cache, tokens, positions, logits_idx,
                         mask, cpos, page_table):
                    return fn(params, cache, tokens, positions, logits_idx,
                              mask, cpos, page_table)
            else:
                def step(params, cache, tokens, positions, logits_idx,
                         mask, cpos):
                    return fn(params, cache, tokens, positions, logits_idx,
                              mask, cpos)

            self._steps[key] = self._jit(step, key=key, donate_argnums=(1,))
        return self._steps[key]

    def pack_ladder(self, chunk: int) -> Tuple[int, ...]:
        """The packed rungs of this engine's mixed step at ``chunk``,
        ascending (:func:`pack_widths` less its widest, which is the
        padded program). Empty where the step takes no packed token
        axis: the decode step, a family that declares no
        ``PACKED_STEP``, the dense layout, the ring, the fused RoPE
        prologue (it commits K/V inside the kernel, at (R, C))."""
        if (
            not self.paged or self.cp_ring
            or not getattr(self.model, "PACKED_STEP", False)
            or "rope_kv_write" in self.serving.fused_decode
        ):
            return ()
        return pack_widths(self.num_slots, chunk)[:-1]

    def pack_width(self, real: int, chunk: int) -> int:
        """The width a mixed step at ``chunk`` that holds ``real`` real
        tokens runs at: the narrowest rung of :meth:`pack_ladder` that
        holds them, or ``num_slots * chunk``, the padded program, where
        none does. From its arguments alone: :meth:`run_mixed` picks
        its program by it, and the scheduler counts its steps by it."""
        return next((w for w in self.pack_ladder(chunk) if w >= real),
                    self.num_slots * chunk)

    def _get_mixed_step(self, chunk: int, with_logits: bool,
                        sample_mode: str, topk_cap: int,
                        pack: Optional[int] = None):
        """Fused MIXED step — the continuous-batching workhorse: token
        select (device feedback vs host) for column 0 → serve_step over
        (R, chunk) ragged rows (decode rows use one column, prefill rows
        up to ``chunk``; padding sits at the scratch position) →
        per-slot sampling at each row's ``logits_idx``. One program,
        cache donated, sampled tokens stay on device so decode rows AND
        prefill-final rows feed the next step without a host round-trip.
        With ``chunk == 1`` this is exactly the fused decode step (the
        reference's 4-deep batch-future pipeline); larger chunks carry
        chunked prefill in the same dispatch, which is what lets the
        scheduler admit and prefill without ever draining the pipeline.
        ``with_logits`` additionally returns the pre-sampling logits
        (parity tests/debug only — the serving path skips the extra
        output).

        ``sample_mode``/``topk_cap`` (serve/sampling.py): the head the
        program samples with, the one its caller's batch chose
        (:meth:`run_mixed`) — an all-greedy batch's program holds an
        argmax and no (R, V) sort. They tag the key, so each head the
        traffic actually needs compiles exactly once.

        ``pack`` (a rung of :meth:`pack_ladder`): the same step with
        the model's token axis packed at that width, under a key and a
        name of its own (``ff_step_c<chunk>_t<pack>``). A rung returns
        its logits to every caller (they exist on the device anyway,
        for the sampling head), so a caller that wants them runs the
        very program the server runs, and a ladder is compiled once."""
        key_id = ("mixed_fused", chunk, with_logits, sample_mode, topk_cap)
        if pack is not None:
            key_id = ("mixed_packed", chunk, pack, sample_mode, topk_cap)
            with_logits = True
        if key_id not in self._steps:
            fn = self._serve_step_fn(all_logits=False, pack=pack, counts=True)
            paged = self.paged

            def step(params, cache, last_tokens, host_tokens, use_last,
                     positions, logits_idx, key, greedy, temperature,
                     topp, topk, page_table=None):
                with sublayer("glue"):
                    first = jnp.where(use_last, last_tokens, host_tokens[:, 0])
                    tokens = jnp.concatenate(
                        [first[:, None], host_tokens[:, 1:]], axis=1
                    )
                args = (params, cache, tokens, positions, logits_idx,
                        None, None)
                if paged:
                    args = args + (page_table,)
                logits, cache, *counts = fn(*args)
                with sublayer("head"):
                    toks = sample_tokens(
                        logits, key,
                        greedy=greedy, temperature=temperature, topp=topp,
                        topk_arr=topk, mode=sample_mode, topk_cap=topk_cap,
                    )
                out = (toks, logits) if with_logits else (toks,)
                if counts:  # one array to fetch: the tokens, then the counters
                    with sublayer("glue"):
                        out += (jnp.concatenate(
                            [toks]
                            + [c.reshape(-1) for c in counts[0].values()]),)
                return (*out, cache)

            self._steps[key_id] = self._jit(
                step, key=key_id, donate_argnums=(1,)
            )
        return self._steps[key_id]

    def run_mixed(self, last_tokens, host_tokens, use_last, positions,
                  logits_idx, key, greedy, temperature, topp, topk,
                  with_logits: bool = False):
        """Dispatch one fused mixed step over (R, C) host data; returns
        the sampled tokens as a DEVICE array (R,) — the caller fetches
        them up to ``dispatch_ahead`` steps later. ``with_logits``
        additionally returns the (R, V) logits (device array).

        The step samples with the head its batch asks for
        (``choose_sample_mode`` over the host ``greedy`` / ``topp`` /
        ``topk`` arrays, on every dispatch; :attr:`step_head` keeps the
        choice) and runs at :meth:`pack_width` of the real tokens the
        ``positions`` show. Every serving rung of a (chunk, head)
        ladder is lowered and compiled when its first is asked for, so
        a later step that lands on another rung compiles nothing; a
        head no batch has asked for is never compiled."""
        kw = {}
        if self.paged:
            kw["page_table"] = self.page_table_device()
        host_tokens = np.asarray(host_tokens)
        chunk = host_tokens.shape[1]
        pack, ladder = None, self.pack_ladder(chunk)
        if ladder:
            from .kernels import real_query_lengths  # Pallas: not at import

            width = self.pack_width(int(real_query_lengths(
                np.asarray(positions), self.scratch_pos).sum()), chunk)
            pack = width if width <= ladder[-1] else None
        mode, cap = self.step_head = choose_sample_mode(
            greedy, topp, topk, self.cfg.vocab_size
        )
        # every jit-call argument converts with a PINNED dtype: the
        # abstract signature — and so the compile-cache key — must not
        # follow whatever host types the scheduler happened to produce
        # (weak-type/x64 retrace hazard, ffcheck FF103)
        donated = self.cache
        self.count_dispatch("mixed")
        with _set_mesh(self.mesh):
            step = self._get_mixed_step(chunk, with_logits, mode, cap, pack)
            args = (
                self.params,
                self.cache,
                self._carry(last_tokens),
                jnp.asarray(host_tokens, dtype=jnp.int32),
                jnp.asarray(use_last, dtype=jnp.bool_),
                jnp.asarray(positions, dtype=jnp.int32),
                jnp.asarray(logits_idx, dtype=jnp.int32),
                key,
                jnp.asarray(greedy, dtype=jnp.bool_),
                jnp.asarray(temperature, dtype=jnp.float32),
                jnp.asarray(topp, dtype=jnp.float32),
                jnp.asarray(topk, dtype=jnp.int32),
            )
            if (ladder and not with_logits
                    and (chunk, mode, cap) not in self._ladders_compiled):
                # the other rungs take these same arguments: lowering
                # and compiling them here fills the caches the jitted
                # call reads, so their first dispatch compiles nothing
                self._ladders_compiled.add((chunk, mode, cap))
                for width in ladder + (None,):
                    if width != pack:
                        self._get_mixed_step(
                            chunk, False, mode, cap, width
                        ).lower(*args, **kw).compile()
            out = step(*args, **kw)
        toks, *rest, self.cache = out
        if self._step_counts:
            from ..models.transformer import routed_tile

            self.step_fetch = rest.pop()
            self.step_tile = routed_tile(
                pack or host_tokens.size, *self._expert_routing)
        self._poison_donated(
            donated, ("mixed_packed", chunk, pack, mode, cap) if pack
            else ("mixed_fused", chunk, with_logits, mode, cap))
        return (toks, *rest) if with_logits else toks

    def split_fetch(self, fetched: np.ndarray):
        """A fetched ``step_fetch`` as (sampled tokens (R,), {name:
        counters}) by the family's ``step_counts`` shapes."""
        toks, rest = fetched[:self.num_slots], fetched[self.num_slots:]
        counts = {}
        for name, shape in self._step_counts.items():
            n = math.prod(shape)
            counts[name], rest = rest[:n].reshape(shape), rest[n:]
        return toks, counts

    def run_decode(self, last_tokens, host_tokens, use_last, positions,
                   key, greedy, temperature, topp, topk=None):
        """Dispatch one fused decode step (the C == 1 mixed step);
        returns the sampled tokens as a DEVICE array (R,) — the caller
        fetches it a step later."""
        R = self.num_slots
        if topk is None:
            topk = np.zeros((R,), np.int32)
        return self.run_mixed(
            last_tokens, host_tokens, use_last, positions,
            np.zeros((R,), np.int32), key, greedy, temperature, topp, topk,
        )

    def _get_step_sampled(self, chunk: int, with_mask: bool,
                          sample_mode: str, topk_cap: int,
                          with_logits: bool = False):
        """The SYNC step that samples on the device: serve_step plus
        the decode head its batch chose in ONE compiled program, cache
        donated — where the two-dispatch sync step (:meth:`run`, then
        the scheduler's host-side ``sample_tokens``) dispatches two
        programs per step, this dispatches one and keeps the logits on
        device. ``with_logits`` additionally returns them (parity
        tests; the serving path skips the extra output)."""
        key_id = ("step_sampled", chunk, with_mask, sample_mode, topk_cap,
                  with_logits)
        if key_id not in self._steps:
            fn = self._serve_step_fn(all_logits=False)
            paged = self.paged

            def step(params, cache, tokens, positions, logits_idx, mask,
                     cpos, key, greedy, temperature, topp, topk,
                     page_table=None):
                args = (params, cache, tokens, positions, logits_idx,
                        mask, cpos)
                if paged:
                    args = args + (page_table,)
                logits, cache = fn(*args)
                with sublayer("head"):
                    toks = sample_tokens(
                        logits, key,
                        greedy=greedy, temperature=temperature, topp=topp,
                        topk_arr=topk, mode=sample_mode, topk_cap=topk_cap,
                    )
                if with_logits:
                    return toks, logits, cache
                return toks, cache

            self._steps[key_id] = self._jit(
                step, key=key_id, donate_argnums=(1,)
            )
        return self._steps[key_id]

    def run_sampled(self, bc: BatchConfig, key, greedy, temperature, topp,
                    topk, with_logits: bool = False):
        """Dispatch one SYNC step that samples on the device (the sync
        scheduler's step wherever the manager's
        ``supports_fused_sampling`` holds): one program computes the
        step's logits at each row's ``logits_idx`` AND samples them
        with the head the batch chose, so the (R, V) logits never reach
        the host. Returns the sampled tokens as a device array (R,) —
        plus the logits when ``with_logits``."""
        if self.serving.inference_debugging:
            with _set_mesh(self.mesh):
                self._dump_debug(bc)
        mode, cap = choose_sample_mode(
            greedy, topp, topk, self.cfg.vocab_size
        )
        args = (
            jnp.asarray(bc.tokens, dtype=jnp.int32),
            jnp.asarray(bc.positions, dtype=jnp.int32),
            jnp.asarray(bc.logits_idx, dtype=jnp.int32),
            jnp.asarray(bc.mask, dtype=jnp.bool_)
            if bc.mask is not None else None,
            jnp.asarray(bc.cache_positions, dtype=jnp.int32)
            if bc.cache_positions is not None
            else None,
            key,
            jnp.asarray(greedy, dtype=jnp.bool_),
            jnp.asarray(temperature, dtype=jnp.float32),
            jnp.asarray(topp, dtype=jnp.float32),
            jnp.asarray(topk, dtype=jnp.int32),
        )
        kw = {}
        if self.paged:
            kw["page_table"] = self.page_table_device()
        donated = self.cache
        self.count_dispatch("step_sampled")
        with _set_mesh(self.mesh):
            step = self._get_step_sampled(
                bc.chunk, bc.mask is not None, mode, cap, with_logits
            )
            out = step(self.params, self.cache, *args, **kw)
        if with_logits:
            toks, logits, self.cache = out
            self._poison_donated(
                donated, ("step_sampled", bc.chunk, bc.mask is not None)
            )
            return toks, logits
        toks, self.cache = out
        self._poison_donated(
            donated, ("step_sampled", bc.chunk, bc.mask is not None)
        )
        return toks

    def _get_speculate(self, W: int, D: int,
                       num_layers: Optional[int] = None):
        """Whole-tree SSM speculation as ONE compiled program: a scan
        over beam depths, each feeding the W-wide frontier through
        serve_step (tree-mask mode), expanding top-W-of-(W*V) children
        with cumulative logprobs, and writing K/V at the device-computed
        slack lines (prefix + 1 + d*W + w). Replaces the host round-trip
        per depth the reference pays once per beam step too
        (prepare_next_batch_beam); the host fetches the finished tree in
        a single transfer.

        One program per (W, D[, num_layers]) — adaptive tree shaping
        moves requests along a BUCKETED W×D ladder (serve/specinfer.py
        SpecConfig.bucket_ladder), so the key set stays bounded by the
        ladder, never free-form. ``num_layers`` is the self-speculation
        early-exit draft: the frontier expands through a layer-sliced
        step over THIS engine's own params + cache."""
        key_id = ("speculate", W, D)
        if num_layers is not None:
            key_id = key_id + (int(num_layers),)
        if key_id not in self._steps:
            fn = self._serve_step_fn(all_logits=True, num_layers=num_layers)
            from .sampling import log_softmax

            R = self.num_slots
            S1 = self.serving.cache_len + 1
            scratch = self.scratch_pos
            NEG = -1e30

            paged = self.paged

            def speculate(params, cache, root_tokens, prefix, active,
                          page_table=None):
                key_pos = jnp.arange(S1, dtype=jnp.int32)
                # frontier state, beam dim = W; only w0 live at depth 0
                w_iota = jnp.arange(W, dtype=jnp.int32)
                f_tok = jnp.where(
                    (w_iota == 0)[None, :], root_tokens[:, None], 0
                ).astype(jnp.int32)
                f_valid = (w_iota == 0)[None, :] & active[:, None]
                f_cum = jnp.where(f_valid, 0.0, NEG).astype(jnp.float32)
                f_line = jnp.where(
                    f_valid, prefix[:, None], scratch
                ).astype(jnp.int32)
                committed = key_pos[None, :] < prefix[:, None]  # (R, S1)
                f_mask = (
                    committed[:, None, :]
                    | (key_pos[None, None, :] == f_line[:, :, None])
                ) & f_valid[:, :, None]

                def body(carry, d):
                    cache, f_tok, f_cum, f_valid, f_mask, f_line = carry
                    pos = jnp.where(
                        f_valid, prefix[:, None] + d, scratch
                    ).astype(jnp.int32)
                    args = (params, cache, f_tok, pos,
                            jnp.zeros((R,), jnp.int32), f_mask, f_line)
                    if paged:
                        args = args + (page_table,)
                    logits, cache = fn(*args)  # (R, W, V)
                    V = logits.shape[-1]
                    logp = log_softmax(logits) + f_cum[:, :, None]
                    logp = jnp.where(f_valid[:, :, None], logp, NEG)
                    vals, flat = jax.lax.top_k(logp.reshape(R, W * V), W)
                    parent = (flat // V).astype(jnp.int32)
                    token = (flat % V).astype(jnp.int32)
                    child_valid = (vals > NEG / 2) & active[:, None]
                    new_line = jnp.where(
                        child_valid,
                        prefix[:, None] + 1 + d * W + w_iota[None, :],
                        scratch,
                    ).astype(jnp.int32)
                    parent_mask = jnp.take_along_axis(
                        f_mask, parent[:, :, None], axis=1
                    )
                    new_mask = (
                        parent_mask
                        | (key_pos[None, None, :] == new_line[:, :, None])
                    ) & child_valid[:, :, None]
                    carry = (cache, token, vals, child_valid, new_mask, new_line)
                    return carry, (token, parent, vals)

                init = (cache, f_tok, f_cum, f_valid, f_mask, f_line)
                (cache, *_), (toks, parents, logps) = jax.lax.scan(
                    body, init, jnp.arange(D, dtype=jnp.int32)
                )
                return toks, parents, logps, cache  # each (D, R, W)

            self._steps[key_id] = self._jit(
                speculate, key=key_id, donate_argnums=(1,)
            )
        return self._steps[key_id]

    def run_speculate(self, root_tokens, prefix, active, W: int, D: int,
                      num_layers: Optional[int] = None):
        """Dispatch one whole speculation round; returns device arrays
        (tokens, parents, logps) each (D, R, W). The cache advances in
        place with every tree node's K/V at its slack line.
        ``num_layers`` drafts through the layer-sliced early-exit step
        (self-speculation: this engine doubles as its own SSM)."""
        kw = {}
        if self.paged:
            kw["page_table"] = self.page_table_device()
        donated = self.cache
        self.count_dispatch("speculate")
        with _set_mesh(self.mesh):
            step = self._get_speculate(W, D, num_layers)
            toks, parents, logps, self.cache = step(
                self.params,
                self.cache,
                jnp.asarray(root_tokens, jnp.int32),
                jnp.asarray(prefix, jnp.int32),
                jnp.asarray(active, dtype=jnp.bool_),
                **kw,
            )
        self._poison_donated(donated, ("speculate", W, D, num_layers))
        return toks, parents, logps

    def _dump_debug(self, bc: BatchConfig):
        """inference_debugging: eager per-layer forward on the CURRENT
        cache (read-only — must run before the donating step), each
        layer's hidden states to .npy (reference per-op tensor dumps)."""
        import os

        fn = getattr(self.model, "serve_debug_activations", None)
        if fn is None:
            # loud skip, never a silent no-op (ADVICE.md round 5): the
            # family module lacks the hook, so nothing can be dumped —
            # warn once and keep serving at full speed (the
            # RequestManager only downgrades fast decode when the hook
            # exists, request_manager.py).
            if not getattr(self, "_warned_no_debug_hook", False):
                from ..logging_utils import get_logger

                get_logger("serve").warning(
                    "inference_debugging is enabled but %s has no "
                    "serve_debug_activations hook — nothing will be "
                    "dumped for this engine",
                    getattr(self.model, "__name__", repr(self.model)),
                )
                self._warned_no_debug_hook = True
            return
        # per-engine subdirectory: a SpecInfer pair (LLM + SSM engines)
        # shares the dump dir, and both counters start at 0 — same-named
        # files would silently overwrite across engines
        outdir = os.path.join(
            self.serving.inference_debugging,
            f"{self.model.__name__.rsplit('.', 1)[-1]}-"
            f"L{self.cfg.num_hidden_layers}-{id(self) & 0xFFFF:04x}",
        )
        os.makedirs(outdir, exist_ok=True)
        kw = dict(cfg=self.cfg, kernels=self.serving.kernels)
        if self.paged:
            kw["page_table"] = self.page_table_device()
            kw["cache_len"] = self.serving.cache_len
            if self.serving.kv_quant is not None:
                kw["kv_quant"] = self.serving.kv_quant
        acts = fn(
            self.params, self.cache, jnp.asarray(bc.tokens, dtype=jnp.int32),
            jnp.asarray(bc.positions, dtype=jnp.int32),
            jnp.asarray(bc.mask, dtype=jnp.bool_)
            if bc.mask is not None else None,
            jnp.asarray(bc.cache_positions, dtype=jnp.int32)
            if bc.cache_positions is not None else None,
            **kw,
        )
        step = self._debug_step
        np.save(os.path.join(outdir, f"step{step:05d}_tokens.npy"),
                np.asarray(bc.tokens))
        np.save(os.path.join(outdir, f"step{step:05d}_positions.npy"),
                np.asarray(bc.positions))
        for l, h in enumerate(acts):
            np.save(
                os.path.join(outdir, f"step{step:05d}_layer{l:03d}.npy"),
                # ffcheck: disable=FF107 -- inference_debugging triage dump: deliberately slow, forced off the fast path by the RequestManager
                np.asarray(jax.device_get(h)),
            )
        self._debug_step += 1

    def run(self, bc: BatchConfig, all_logits: bool = False):
        """Dispatch one step (reference ``InferenceManager::inference``,
        inference_manager.cc:334). Returns logits on device; the cache is
        advanced in place (donated)."""
        if self.serving.inference_debugging:
            with _set_mesh(self.mesh):
                self._dump_debug(bc)
        args = (
            jnp.asarray(bc.tokens, dtype=jnp.int32),
            jnp.asarray(bc.positions, dtype=jnp.int32),
            jnp.asarray(bc.logits_idx, dtype=jnp.int32),
            jnp.asarray(bc.mask, dtype=jnp.bool_)
            if bc.mask is not None else None,
            jnp.asarray(bc.cache_positions, dtype=jnp.int32)
            if bc.cache_positions is not None
            else None,
        )
        if self.paged:
            # the engine's own table is authoritative (a SpecInfer pair
            # shares one BatchConfig across engines whose pools differ);
            # bc.page_table is carried as host-side metadata
            args = args + (self.page_table_device(),)
        donated = self.cache
        self.count_dispatch("step")
        with _set_mesh(self.mesh):
            step = self._get_step(bc.chunk, all_logits, bc.mask is not None)
            logits, self.cache = step(self.params, self.cache, *args)
        self._poison_donated(
            donated, (bc.chunk, all_logits, bc.mask is not None)
        )
        return logits

    def copy_page(self, src: int, dst: int):
        """Device-side copy of one physical page's K/V lines across all
        layers (prefix-cache copy-on-write, serve/prefix_cache.py:
        a request that must append into a SHARED cached tail page gets a
        private copy first). One jitted program, page ids traced — the
        compile is paid once."""
        if "copy_page" not in self._steps:
            self._steps["copy_page"] = self._jit(
                self.model.copy_page_kv, key="copy_page",
                donate_argnums=(0,),
            )
        donated = self.cache
        self.count_dispatch("copy_page")
        with _set_mesh(self.mesh):
            self.cache = self._steps["copy_page"](
                self.cache,
                jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32),
            )
        self._poison_donated(donated, "copy_page")

    def fetch_page(self, page: int):
        """Device→host SPILL read of one physical page (hierarchical KV
        cache, serve/prefix_cache.py host tier): one jitted program
        slices the page's content out of every cache buffer —
        K/V codes, quantized scale rows, the generic decoder's position
        lines — and an ASYNC host copy starts on each slice. Returns
        the slice pytree immediately; the caller converts to host
        arrays later (PrefixCache.harvest, at the scheduler's existing
        flush sync point), so a spill never stalls a decode step
        (ffcheck FF107 is the lint guard for that contract). The slice
        buffers are data-independent of the pool from the moment the
        program is enqueued, so freeing and reusing the page cannot
        corrupt the copy."""
        if "fetch_page" not in self._steps:
            self._steps["fetch_page"] = self._jit(
                self.model.gather_page_kv, key="fetch_page"
            )
        self.count_dispatch("fetch_page")
        with _set_mesh(self.mesh):
            out = self._steps["fetch_page"](
                self.cache, jnp.asarray(page, jnp.int32)
            )
        for leaf in jax.tree.leaves(out):
            leaf.copy_to_host_async()
        return out

    def upload_page(self, page: int, values) -> None:
        """Host→device RE-ADMIT of a previously spilled page: one
        jitted program (cache donated) writes the spilled content back
        into pool row ``page``. ``values`` is whatever
        :meth:`fetch_page` returned — harvested numpy arrays, or the
        original device slices if the spill was never harvested (the
        transfer then stays device-side). ``jax.device_put`` semantics
        are async: the upload overlaps the host loop and orders before
        the prefill step that reads the page."""
        if "upload_page" not in self._steps:
            self._steps["upload_page"] = self._jit(
                self.model.scatter_page_kv, key="upload_page",
                donate_argnums=(0,),
            )
        dtypes = {k: v.dtype for k, v in self.cache.items()}
        donated = self.cache
        self.count_dispatch("upload_page")
        with _set_mesh(self.mesh):
            self.cache = self._steps["upload_page"](
                self.cache,
                jnp.asarray(page, jnp.int32),
                {
                    k: jnp.asarray(v, dtype=dtypes[k])
                    for k, v in values.items()
                },
            )
        self._poison_donated(donated, "upload_page")

    def page_host_bytes(self) -> int:
        """Host bytes one spilled page occupies (every cache buffer's
        per-page slice) — prices the ``host_cache_bytes`` budget."""
        shapes = jax.eval_shape(
            self.model.gather_page_kv,
            jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self.cache,
            ),
            jax.ShapeDtypeStruct((), jnp.int32),
        )
        return sum(
            int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(shapes)
        )

    def reorder(self, src_slots: np.ndarray):
        """Slot permutation/gather of the whole cache (beam search
        hypothesis reordering): new slot r holds old slot src_slots[r].
        Paged layout: page ownership stays put, page CONTENT is copied
        through the table (model.reorder_slots_paged)."""
        if "reorder" not in self._steps:
            if self.paged:
                self._steps["reorder"] = self._jit(
                    self.model.reorder_slots_paged, key="reorder",
                    donate_argnums=(0,),
                )
            else:
                self._steps["reorder"] = self._jit(
                    self.model.reorder_slots, key="reorder",
                    donate_argnums=(0,),
                )
        donated = self.cache
        self.count_dispatch("reorder")
        with _set_mesh(self.mesh):
            if self.paged:
                self.cache = self._steps["reorder"](
                    self.cache, self.page_table_device(),
                    jnp.asarray(src_slots, jnp.int32),
                )
            else:
                self.cache = self._steps["reorder"](
                    self.cache, jnp.asarray(src_slots, jnp.int32)
                )
        self._poison_donated(donated, "reorder")

    def commit(self, src: np.ndarray, dst: np.ndarray):
        """Move accepted speculative cache lines to committed positions
        (src/dst (R, K); unused entries scratch→scratch)."""
        if self._commit is None:
            if self.paged:
                fn = self.model.commit_kv_paged
                if self.serving.kv_quant is not None:
                    # quantized pools dequant/requant moved lines at the
                    # page scales (models/*.commit_kv_paged)
                    fn = functools.partial(
                        fn, kv_quant=self.serving.kv_quant
                    )
            else:
                fn = self.model.commit_kv
            self._commit = self._jit(fn, key="commit", donate_argnums=(0,))
        donated = self.cache
        self.count_dispatch("commit")
        with _set_mesh(self.mesh):
            if self.paged:
                self.cache = self._commit(
                    self.cache, self.page_table_device(),
                    jnp.asarray(src, dtype=jnp.int32),
                    jnp.asarray(dst, dtype=jnp.int32),
                )
            else:
                self.cache = self._commit(
                    self.cache, jnp.asarray(src, dtype=jnp.int32),
                    jnp.asarray(dst, dtype=jnp.int32),
                )
        self._poison_donated(donated, "commit")

    def reset(self):
        """Drop all cached sequences (fresh KV cache; paged: fresh
        allocator — all pages back on the free list). Any PrefixCache
        built over the old allocator is invalidated with it — managers
        are expected to be rebuilt alongside an engine reset."""
        self.cache = self._alloc_cache()

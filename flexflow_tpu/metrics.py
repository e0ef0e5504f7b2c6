"""Training metrics.

TPU-native equivalent of the reference Metrics op (reference
``src/metrics_functions/metrics_functions.cc``, ``include/flexflow/
metrics_functions.h:44-88``): per-shard metrics computed on device and
folded into a ``PerfMetrics`` running aggregate. Here metrics are computed
inside the jitted step (GSPMD reduces across data shards automatically)
and aggregated on host with :class:`PerfMetrics`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ACCURACY = "accuracy"
CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
MEAN_SQUARED_ERROR = "mean_squared_error"
MEAN_ABSOLUTE_ERROR = "mean_absolute_error"

#: bound of a ClusterStats wall-time reservoir (note_cluster_step_ms, ...)
_STEP_MS_CAP = 4096


def compute_metrics(
    metric_names: Sequence[str],
    preds,
    labels,
    *,
    sparse_labels: bool,
    from_logits: bool = True,
) -> Dict[str, jnp.ndarray]:
    """Returns dict of scalar metric values for one batch (device-side)."""
    out = {}
    pf = preds.astype(jnp.float32)
    for m in metric_names:
        if m == ACCURACY:
            if sparse_labels:
                hit = jnp.argmax(pf, axis=-1).astype(jnp.int32) == labels.reshape(
                    pf.shape[:-1]
                ).astype(jnp.int32)
            else:
                hit = jnp.argmax(pf, axis=-1) == jnp.argmax(labels, axis=-1)
            out[m] = hit.mean()
        elif m in (CATEGORICAL_CROSSENTROPY,):
            lp = jnp.log(jnp.clip(pf, 1e-12, 1.0))
            out[m] = -(labels.astype(jnp.float32) * lp).sum(-1).mean()
        elif m == SPARSE_CATEGORICAL_CROSSENTROPY:
            if from_logits:
                lp = jax.nn.log_softmax(pf, axis=-1)
            else:
                lp = jnp.log(jnp.clip(pf, 1e-12, 1.0))
            lbl = labels.reshape(pf.shape[:-1]).astype(jnp.int32)
            out[m] = -jnp.take_along_axis(lp, lbl[..., None], -1).mean()
        elif m == MEAN_SQUARED_ERROR:
            d = pf - labels.astype(jnp.float32)
            out[m] = (d * d).mean()
        elif m == MEAN_ABSOLUTE_ERROR:
            out[m] = jnp.abs(pf - labels.astype(jnp.float32)).mean()
        else:
            raise ValueError(f"unknown metric {m!r}")
    return out


@dataclasses.dataclass
class SchedulerStats:
    """Host-side continuous-batching telemetry aggregated per scheduler
    step (the serving analog of :class:`PerfMetrics`): slot occupancy,
    prefill token-budget fill, pipeline behavior (drains = full flushes,
    the expensive sync points continuous batching exists to avoid), and
    request lifecycle counters. The RequestManager updates it on every
    dispatch/flush; the bench and ``FF_LOG=serve=debug`` read it."""

    steps: int = 0
    mixed_steps: int = 0          # pipelined mixed prefill+decode steps
    decode_steps: int = 0         # pipelined pure-decode steps
    sync_steps: int = 0           # blocking host-round-trip steps
    flushes: int = 0              # in-flight entries drained to host
    pipeline_drains: int = 0      # full _flush_all with work in flight
    admitted: int = 0
    preemptions: int = 0
    failed: int = 0
    prefill_tokens: int = 0       # chunk tokens dispatched
    decode_tokens: int = 0        # decode tokens dispatched
    # lines of context the dispatched decode rows attend, summed (a row
    # at position p: p + 1); over ``decode_tokens``: the mean context a
    # decode row reads, what a full layer's K/V bytes follow
    decode_context_lines: int = 0
    occupancy_sum: float = 0.0    # active slots / total, summed per step
    budget_fill_sum: float = 0.0  # prefill tokens / budget, per mixed step
    # Automatic prefix caching (serve/prefix_cache.py): admissions that
    # reused cached KV pages vs cold admissions, tokens whose prefill
    # the cache skipped, pages published to / evicted from the radix
    # tree, and copy-on-write page copies for partially-matched tails.
    prefix_hits: int = 0
    prefix_misses: int = 0
    prefix_hit_tokens: int = 0
    prefix_inserts: int = 0
    prefix_evictions: int = 0
    prefix_cows: int = 0
    # Hierarchical KV cache host tier (serve/prefix_cache.py spill,
    # ServingConfig.host_cache_bytes): pages spilled device→host
    # instead of evicted, pages re-admitted host→device on a later
    # match, prompt tokens whose prefill a host hit skipped (the
    # recompute the tier saved — also mirrored per-request into
    # ProfileInfo.host_hit_tokens), and the host tier's current byte
    # occupancy (a gauge, not a counter).
    spills: int = 0
    readmits: int = 0
    host_hit_tokens: int = 0
    host_bytes: int = 0
    # SpecInfer adaptive speculation (serve/specinfer.py): per-request
    # verify rounds run, tree tokens DRAFTED by the SSM/early-exit
    # draft, drafted tokens the verifier accepted (root/bonus tokens in
    # neither — see ProfileInfo.speculated_tokens), and W×D ladder
    # moves the acceptance-driven controllers made. FF_LOG=serve=debug
    # reports them alongside the scheduler counters.
    spec_rounds: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_resizes: int = 0
    # Acceptance-weighted verify-skip (SpecConfig.verify_skip):
    # request-rounds that skipped the speculate+verify dispatches and
    # rode the incremental decode path (cold draft), and the periodic
    # smallest-rung re-probe rounds that re-measured the draft.
    verify_skipped_rounds: int = 0
    spec_reprobes: int = 0
    # Context-parallel long-context serving (ServingConfig.kv_shard=
    # "context", serve/paging.py + serve/kernels.py): the shard degree
    # one request's KV pages stripe over (0 = CP off), ring hops a
    # sequence-sharded mesh pays per dispatched attention step
    # ((shards-1) per step — the ppermute stat rotations of ring
    # ragged paged attention), and the pool's striping balance gauge
    # (min/max used pages across shards; 1.0 = perfectly balanced).
    cp_shards: int = 0
    ring_steps: int = 0
    shard_balance: float = 1.0
    # The build log (obs/builds.py; note_build): builds of step programs
    # counted at the engine's jit chokepoint, whose wrapper runs when a
    # program is traced, with or without a sanitizer — and how many of
    # them were RE-builds of an already-built step key, the
    # steady-state perf hazard (``sanitizers=("retrace",)`` makes one
    # raise). Healthy serving: compiles settles after warmup and
    # retraces stays 0. Beside them the seconds those builds took by
    # part — the Python trace, the lowering to MLIR, the backend's
    # compile or the compilation cache's load, which of the two it was
    # (a build the cache was not asked about counts as neither) — the
    # seconds of builds that began while a request was live, so inside
    # a step that the request waited for, the seconds of every other
    # build of the process since the engine's construction (jnp helpers
    # run outside any program, a harness's reference), and the records:
    # name (``ff_step_c1``; ``ff_step_c1#2`` a retrace) -> the numbers
    # of obs.builds.Build, ``other`` -> those builds' sums. The dict is
    # replaced, never updated in place.
    compiles: int = 0
    retraces: int = 0
    build_trace_s: float = 0.0
    build_lower_s: float = 0.0
    build_backend_s: float = 0.0
    build_cache_hits: int = 0
    build_cache_misses: int = 0
    build_in_step_s: float = 0.0
    build_other_s: float = 0.0
    builds: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    # A family with per-slot state beside the page pool (the engine's
    # model declares ``SLOT_STATE``; models/minicpm_sala.py): the bytes
    # of that state (a gauge: held at any context length), the rows of
    # pipelined steps that held a real position, those of them that
    # started at position 0 and so from a zero state (a new request, a
    # preempted one's recompute), and those whose last position lay at
    # or past the family's ``dense_len``, so that the step took the
    # block choice.
    slot_state_bytes: int = 0
    real_rows: int = 0
    state_resets: int = 0
    sparse_rows: int = 0
    # The ragged paged attention kernel's grid over the pipelined steps
    # of a paged engine (note_attn_steps), a layer's call: the grid
    # steps it RUNS (its work list, serve/kernels.ragged_work: the live
    # entries and one step for each row that has none; rows x logical
    # pages before PR 63), those of them that hold a real query and a
    # key it may see, and those of the live ones on rows that take the
    # narrow body.
    attn_steps_grid: int = 0
    attn_steps_live: int = 0
    attn_steps_narrow: int = 0
    # The token axis of the pipelined mixed steps (note_step_tokens):
    # the real tokens they held, the widths they were dispatched at (a
    # packed rung of the engine's ladder, serve/engine.pack_widths; a
    # step that is not packed counts slots x chunk), and the steps by
    # width. The dict is REPLACED on every count, never updated in
    # place, so a copy of the stats keeps the counts of its own moment.
    # ``rung_trims`` are the steps that gave prompt tokens up to stay on
    # a rung (serve/request_manager.trim_to_rung), ``rung_trim_tokens``
    # the tokens given up: each went out in its row's next chunk.
    step_tokens_real: int = 0
    step_tokens_width: int = 0
    rung_trims: int = 0
    rung_trim_tokens: int = 0
    # The routed expert layers of a sparse family that returns its
    # tokens per expert with each pipelined step (``step_counts``;
    # note_expert_counts, at the flush that fetches them), summed over
    # steps and sparse layers: (token, expert) pairs of real tokens
    # computed, experts that were given a token, experts held (a
    # layer's count a layer and step), the fullest expert's tokens, and
    # the row tiles the experts' tokens fill under the step's own tile
    # (serve/kernels ``grouped_tile``: the grouped matmuls' grid steps
    # that hold a row; over ``moe_experts_hit``, the steps that share
    # one fetch of an expert's weights).
    moe_pairs: int = 0
    moe_experts_hit: int = 0
    moe_experts_held: int = 0
    moe_load_max: int = 0
    moe_tiles: int = 0
    # A family some of whose router outputs are no expert's weights
    # (zero-compute experts that return their input,
    # models/longcat_flash.py): the real tokens' pairs on those
    # outputs, and all their pairs (k a real token and layer), summed
    # like the counts above. ``moe_pairs`` stays the pairs on experts
    # HELD; the rest of ``moe_routed_pairs`` fell on absent experts.
    moe_zero_pairs: int = 0
    moe_routed_pairs: int = 0
    # A family whose page pool holds one compressed line a token and
    # layer (a latent pool, models/deepseek_v3.py): the lines the
    # pipelined steps wrote, real tokens x layers.
    latent_lines: int = 0
    # A family that steps a per-slot state by a recurrence a token (it
    # declares ``RECURRENT_STATE``, the cache entry's name:
    # models/olmo_hybrid.py, models/minicpm_sala.py,
    # models/granite_hybrid.py): the updates the pipelined steps made,
    # real tokens x recurrent layers.
    recurrent_updates: int = 0
    # The sampling head of the pipelined steps (note_head): the mixed
    # and decode dispatches, and those whose batch was all greedy and
    # so took the argmax head (serve/sampling.choose_sample_mode).
    head_steps: int = 0
    head_greedy_steps: int = 0
    # An engine whose family declares classes of page
    # (serve/paging.PageClasses), read where a step's pages are
    # reserved: the pages the window classes freed behind their windows
    # (a counter), the most pages each class held at such a boundary,
    # by class and over the window classes together, and the most the
    # window classes WOULD have held there with nothing freed. The
    # dict is replaced, never updated in place.
    window_pages_freed: int = 0
    window_pages_live_peak: int = 0
    window_pages_unfreed_peak: int = 0
    pages_live_peak: Dict[str, int] = dataclasses.field(default_factory=dict)
    steps_by_width: Dict[int, int] = dataclasses.field(default_factory=dict)

    def record_step(
        self,
        kind: str,                # "mixed" | "decode" | "sync"
        *,
        active_slots: int,
        num_slots: int,
        prefill_tokens: int = 0,
        decode_tokens: int = 0,
        budget: int = 0,
        decode_context: int = 0,  # the decode rows' positions + 1, summed
    ) -> None:
        self.steps += 1
        if kind == "mixed":
            self.mixed_steps += 1
            if budget > 0:
                self.budget_fill_sum += prefill_tokens / budget
        elif kind == "decode":
            self.decode_steps += 1
        else:
            self.sync_steps += 1
        self.prefill_tokens += int(prefill_tokens)
        self.decode_tokens += int(decode_tokens)
        self.decode_context_lines += int(decode_context)
        if num_slots > 0:
            self.occupancy_sum += active_slots / num_slots

    def note_head(self, mode: str) -> None:
        """Count one pipelined step by the sampling head it was
        dispatched with (``InferenceEngine.step_head``)."""
        self.head_steps += 1
        self.head_greedy_steps += mode == "greedy"

    def note_rows(self, first, count, dense_len: Optional[int]) -> None:
        """Count one step's rows for a family with per-slot state:
        ``first`` (R,) each row's first position, ``count`` (R,) its
        real positions (0: the row is padding); ``dense_len`` None: the
        family has no block choice."""
        rows = count > 0
        self.real_rows += int(rows.sum())
        self.state_resets += int((rows & (first == 0)).sum())
        if dense_len is not None:
            self.sparse_rows += int((rows & (first + count > dense_len)).sum())

    def note_expert_counts(self, counts, tile: int, zero_pairs=(),
                           routed_pairs=()) -> None:
        """Count one step's routed expert layers: ``counts`` (sparse
        layers, experts held) the real tokens each expert was given,
        ``tile`` the row tile of the step's grouped matmuls
        (``InferenceEngine.step_tile``). A layer that was given none did not
        route (a step that took the all-expert einsum returns zeros)
        and is not counted. ``zero_pairs`` / ``routed_pairs`` (sparse
        layers,): where the family returns them, the real tokens' pairs
        on outputs that are no expert, and all their pairs."""
        self.moe_zero_pairs += int(np.sum(zero_pairs))
        self.moe_routed_pairs += int(np.sum(routed_pairs))
        counts = np.asarray(counts)
        counts = counts[counts.sum(axis=-1) > 0]
        self.moe_pairs += int(counts.sum())
        self.moe_experts_hit += int((counts > 0).sum())
        self.moe_experts_held += int(counts.size)
        self.moe_load_max += int(counts.max(axis=-1).sum())
        self.moe_tiles += int((-(-counts // tile)).sum())

    def note_attn_steps(self, first, count, page_size: int, num_pages: int,
                        narrow: int, window: int = 0) -> None:
        """Count one step's grid of the ragged paged kernel by the
        kernel's own rule (serve/kernels.live_pages, from which the
        device makes the call's work list) under the causal mask:
        ``first`` (R,) each row's first position, ``count`` (R,) its
        real queries (0: a padding row), ``num_pages`` the table's
        entries a row, ``narrow`` the chunk's ``narrow_query_extent``,
        ``window`` the model's sliding window (0: none). An entry is
        live when some real query of the row may see a key of its page;
        the grid runs the live entries and one step for each row that
        has none. A block choice on top of the causal mask
        (models/minicpm_sala.py) can only skip more."""
        from .serve.kernels import live_pages  # Pallas: not at import

        first, count = np.asarray(first), np.asarray(count)
        _, live = live_pages(first, first + count - 1, page_size, num_pages,
                             window)
        self.attn_steps_grid += int(np.maximum(live, 1).sum())
        self.attn_steps_live += int(live.sum())
        self.attn_steps_narrow += int(live[count <= narrow].sum())

    def note_page_classes(self, classes) -> None:
        """Read the allocators of an engine's page classes (name ->
        ``PageAllocator``) at a step boundary, after the step's pages
        were reserved."""
        peak = self.pages_live_peak
        self.pages_live_peak = {
            name: max(peak.get(name, 0), a.used_pages)
            for name, a in classes.items()}
        windowed = [a for a in classes.values() if a.window is not None]
        self.window_pages_live_peak = max(
            self.window_pages_live_peak, sum(a.used_pages for a in windowed))
        self.window_pages_unfreed_peak = max(
            self.window_pages_unfreed_peak,
            sum(a.untrimmed_pages for a in windowed))

    def note_step_tokens(self, real: int, width: int,
                         trimmed: int = 0) -> None:
        """Count one mixed step's token axis: the ``real`` tokens it
        held, the ``width`` it ran at, and the prompt tokens it gave up
        to run there (``trimmed``)."""
        self.step_tokens_real += int(real)
        self.step_tokens_width += int(width)
        self.rung_trims += trimmed > 0
        self.rung_trim_tokens += int(trimmed)
        by = self.steps_by_width
        self.steps_by_width = {**by, int(width): by.get(int(width), 0) + 1}

    def note_build(self, label: str, numbers: Dict[str, Any], part: str,
                   seconds: float) -> None:
        """Count one event of a build (obs/builds.py): ``part`` is
        ``begin`` (a step program's traced function returned: one
        compile, a retrace where its ordinal is over 1), or the part
        that took ``seconds`` — ``trace``, ``lower``, ``backend``.
        ``label`` names the record, ``numbers`` is its newest state."""
        if part == "begin":
            self.compiles += 1
            self.retraces += numbers["ordinal"] > 1
        elif label == "other":
            self.build_other_s += seconds
        else:
            if part == "trace":
                self.build_trace_s += seconds
            elif part == "lower":
                self.build_lower_s += seconds
            else:
                self.build_backend_s += seconds
                self.build_cache_hits += numbers["cache"] == "hit"
                self.build_cache_misses += numbers["cache"] == "miss"
            if numbers["in_step"]:
                self.build_in_step_s += seconds
        self.builds = {**self.builds, label: numbers}

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0

    @property
    def mean_budget_fill(self) -> float:
        return (
            self.budget_fill_sum / self.mixed_steps if self.mixed_steps else 0.0
        )

    @property
    def pack_fill(self) -> float:
        """Real tokens over dispatched width, over the mixed steps."""
        if not self.step_tokens_width:
            return 0.0
        return self.step_tokens_real / self.step_tokens_width

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admissions that reused at least one cached page."""
        n = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / n if n else 0.0

    @property
    def host_hit_rate(self) -> float:
        """Fraction of prefix-cache hit tokens served from the HOST
        tier (re-admitted spilled pages) rather than live HBM pages —
        how much of the cache's value survived memory pressure thanks
        to spilling instead of eviction."""
        if not self.prefix_hit_tokens:
            return 0.0
        return self.host_hit_tokens / self.prefix_hit_tokens

    @property
    def spec_accept_rate(self) -> float:
        """Drafted-accept rate: drafted tokens the verifier accepted
        over drafted tokens — the honest speculation-efficiency figure
        (free root/bonus tokens in neither side)."""
        if not self.spec_drafted:
            return 0.0
        return self.spec_accepted / self.spec_drafted

    def snapshot(self) -> Dict[str, float]:
        return {
            "steps": self.steps,
            "mixed_steps": self.mixed_steps,
            "decode_steps": self.decode_steps,
            "sync_steps": self.sync_steps,
            "flushes": self.flushes,
            "pipeline_drains": self.pipeline_drains,
            "admitted": self.admitted,
            "preemptions": self.preemptions,
            "failed": self.failed,
            "prefill_tokens": self.prefill_tokens,
            "decode_context_lines": self.decode_context_lines,
            "decode_tokens": self.decode_tokens,
            "mean_occupancy": round(self.mean_occupancy, 4),
            "mean_budget_fill": round(self.mean_budget_fill, 4),
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_hit_rate": round(self.prefix_hit_rate, 4),
            "prefix_inserts": self.prefix_inserts,
            "prefix_evictions": self.prefix_evictions,
            "prefix_cows": self.prefix_cows,
            "spills": self.spills,
            "readmits": self.readmits,
            "host_hit_tokens": self.host_hit_tokens,
            "host_hit_rate": round(self.host_hit_rate, 4),
            "host_bytes": self.host_bytes,
            "spec_rounds": self.spec_rounds,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_resizes": self.spec_resizes,
            "verify_skipped_rounds": self.verify_skipped_rounds,
            "spec_reprobes": self.spec_reprobes,
            "spec_accept_rate": round(self.spec_accept_rate, 4),
            "cp_shards": self.cp_shards,
            "ring_steps": self.ring_steps,
            "shard_balance": round(self.shard_balance, 4),
            "compiles": self.compiles,
            "retraces": self.retraces,
            "build_trace_s": self.build_trace_s,
            "build_lower_s": self.build_lower_s,
            "build_backend_s": self.build_backend_s,
            "build_cache_hits": self.build_cache_hits,
            "build_cache_misses": self.build_cache_misses,
            "build_in_step_s": self.build_in_step_s,
            "build_other_s": self.build_other_s,
            # without the by-name ``inner``: a snapshot rides every RPC
            # envelope of a remote replica
            "builds": {
                label: {k: v for k, v in rec.items() if k != "inner"}
                for label, rec in self.builds.items()},
            "step_tokens_real": self.step_tokens_real,
            "step_tokens_width": self.step_tokens_width,
            "pack_fill": round(self.pack_fill, 4),
            "rung_trims": self.rung_trims,
            "rung_trim_tokens": self.rung_trim_tokens,
            "head_steps": self.head_steps,
            "head_greedy_steps": self.head_greedy_steps,
            "steps_by_width": dict(sorted(self.steps_by_width.items())),
            "window_pages_freed": self.window_pages_freed,
            "window_pages_live_peak": self.window_pages_live_peak,
            "window_pages_unfreed_peak": self.window_pages_unfreed_peak,
            "pages_live_peak": dict(self.pages_live_peak),
        }

    def report(self) -> str:
        s = self.snapshot()
        return (
            f"[serve {s['steps']} steps] "
            f"mixed={s['mixed_steps']} decode={s['decode_steps']} "
            f"sync={s['sync_steps']} drains={s['pipeline_drains']} "
            f"occ={s['mean_occupancy']:.2f} fill={s['mean_budget_fill']:.2f} "
            f"prefill_toks={s['prefill_tokens']} "
            f"decode_toks={s['decode_tokens']} adm={s['admitted']} "
            f"preempt={s['preemptions']} failed={s['failed']} "
            f"pfx_hit={s['prefix_hits']}/{s['prefix_hits'] + s['prefix_misses']}"
            f" pfx_toks={s['prefix_hit_tokens']} "
            f"pfx_evict={s['prefix_evictions']} pfx_cow={s['prefix_cows']} "
            f"spill={s['spills']} readmit={s['readmits']} "
            f"host_toks={s['host_hit_tokens']} host_B={s['host_bytes']} "
            f"spec={s['spec_accepted']}/{s['spec_drafted']}"
            f"@{s['spec_rounds']}r resize={s['spec_resizes']} "
            f"vskip={s['verify_skipped_rounds']} "
            f"reprobe={s['spec_reprobes']} "
            f"cp={s['cp_shards']} ring={s['ring_steps']} "
            f"bal={s['shard_balance']:.2f} "
            f"compiles={s['compiles']} retraces={s['retraces']} "
            f"build={s['build_trace_s']:.1f}+{s['build_lower_s']:.1f}"
            f"+{s['build_backend_s']:.1f}s in_step={s['build_in_step_s']:.1f}s "
            f"greedy_head={s['head_greedy_steps']}/{s['head_steps']} "
            f"pack={s['step_tokens_real']}/{s['step_tokens_width']} by width "
            + (",".join(f"{w}:{n}" for w, n in s["steps_by_width"].items())
               or "-")
            + f" trims={s['rung_trims']}/{s['rung_trim_tokens']}tok"
        )


@dataclasses.dataclass
class ClusterStats:
    """Cluster-level serving telemetry (serve/cluster/): the front-end
    router's own counters plus an aggregation hook over every replica's
    :class:`SchedulerStats`. The ClusterManager updates the router
    counters at placement/shed/migration time and passes the per-replica
    stats as CALLABLES (the same indirection SchedulerStats uses for the
    prefix cache and retrace guard), so bench-style stat swaps
    (``rm.stats = SchedulerStats()``) keep counting."""

    submitted: int = 0
    # placements by HOW the router decided: "prefix" (longest radix-tree
    # match), "affinity" (session stickiness), "round_robin",
    # "least_loaded" (policy or prefix-miss fallback)
    placements: Dict[str, int] = dataclasses.field(default_factory=dict)
    affinity_hits: int = 0
    sheds: int = 0                 # SLO admission rejections (ERROR, not hangs)
    migrations: int = 0            # prefill→decode page hand-offs
    migrated_pages: int = 0
    migrated_bytes: int = 0
    # Fault tolerance (serve/cluster/health.py + manager failover):
    # step exceptions observed, replica state transitions (DOWN trips /
    # half-open probes / closed circuits), requests re-admitted off a
    # dead replica through recompute, total re-admission attempts
    # (failovers + migration-drain recomputes), and requests that ended
    # in a terminal error because retries exhausted or no healthy
    # replica remained (the bounded alternative to a hang).
    step_faults: int = 0
    replica_down: int = 0
    replica_suspect: int = 0
    probes: int = 0
    replica_recoveries: int = 0
    failovers: int = 0
    retries: int = 0
    failover_errors: int = 0
    # Migration back-pressure (ServingConfig.migration_queue_budget):
    # failed migrate attempts (exceptions, retried with backoff), the
    # bounded queue's current depth (gauge) and high-water mark, and
    # held prefills that overflowed the budget and drained through
    # recompute re-admission instead of parking with their pages.
    migration_failures: int = 0
    migration_queue_depth: int = 0
    migration_queue_peak: int = 0
    migration_queue_overflows: int = 0
    # Replica RPC transport (serve/cluster/transport.py + remote.py):
    # RPCs that exhausted their retries (each one is also a health
    # observation), retry attempts the deadline/backoff machinery
    # spent (absorbed losses — no health impact), cluster steps on
    # which a remote replica had had no successful exchange for
    # heartbeat_gap_steps (each one a SUSPECT observation), transport
    # reconnects after a disconnect, standby replicas that adopted a
    # DOWN replica's routing position (+ prefix families), and raw
    # frame bytes both ways (requests+responses; migrated page bytes
    # and shipped radix trees dominate).
    rpc_errors: int = 0
    rpc_retries: int = 0
    heartbeat_gaps: int = 0
    reconnects: int = 0
    standby_adoptions: int = 0
    wire_bytes_sent: int = 0
    wire_bytes_received: int = 0
    # Concurrent cluster stepping (serve/cluster/manager.py): the
    # high-water mark of RPCs in flight inside one step's fan-out
    # (gauge — 0 under the serial reference loop), plus bounded
    # reservoirs of whole-cluster-step wall time and per-replica
    # step-RPC round-trip time in milliseconds. The raw sample lists
    # stay out of Prometheus; the derived ``cluster_step_ms_p50/p99``
    # and ``rpc_rtt_ms_p50/p99`` properties export as gauges, and
    # per-replica RTT percentiles ride the snapshot under
    # ``rpc_rtt_ms_per_replica``.
    rpc_inflight_peak: int = 0
    cluster_step_ms_samples: List[float] = dataclasses.field(
        default_factory=list
    )
    rpc_rtt_ms_samples: Dict[int, List[float]] = dataclasses.field(
        default_factory=dict
    )
    # Elastic control plane (serve/cluster/{journal,reconfigure}.py):
    # committed reconfigurations by kind (replicas added live, replicas
    # drained + retired, prefill/decode pool flips), journal traffic
    # (records + raw frame bytes appended; compactions that rewrote the
    # log to the live set), manager restarts recovered from the journal,
    # and unfinished requests a recovery re-admitted through recompute.
    scale_outs: int = 0
    scale_ins: int = 0
    pool_flips: int = 0
    journal_records: int = 0
    journal_bytes: int = 0
    journal_compactions: int = 0
    manager_recoveries: int = 0
    journal_replayed: int = 0
    # Self-driving serving (serve/autotune): policy decisions taken
    # (applied or advisory), speculation-bucket retunes advised, and
    # the predicted-vs-measured throughput gauges the autoscaler
    # refreshes every evaluation (tokens/sec — how far off the cost
    # model is on this box). Per-replica arrival/completion counters
    # and the bounded admission-time queue-delay reservoir feed the
    # TrafficEstimator; the dict fields stay out of Prometheus (the
    # derived ``queue_delay_s_p50/p99`` and the per-replica snapshot
    # maps ride along instead).
    autoscale_decisions: int = 0
    retunes: int = 0
    autoscale_predicted_tps: float = 0.0
    autoscale_measured_tps: float = 0.0
    arrivals_per_replica: Dict[int, int] = dataclasses.field(
        default_factory=dict
    )
    completions_per_replica: Dict[int, int] = dataclasses.field(
        default_factory=dict
    )
    queue_delay_s_samples: List[float] = dataclasses.field(
        default_factory=list
    )

    def record_placement(self, how: str) -> None:
        self.placements[how] = self.placements.get(how, 0) + 1
        if how == "affinity":
            self.affinity_hits += 1

    def note_cluster_step_ms(self, ms: float) -> None:
        """Record one whole-cluster-step wall sample (bounded
        reservoir: the newest ``_STEP_MS_CAP`` samples are kept)."""
        s = self.cluster_step_ms_samples
        s.append(float(ms))
        if len(s) > _STEP_MS_CAP:
            del s[: len(s) - _STEP_MS_CAP]

    def note_arrival(self, replica: int) -> None:
        """Count one first-time placement onto ``replica`` (failover
        re-admissions are NOT arrivals — the request already counted)."""
        r = int(replica)
        self.arrivals_per_replica[r] = self.arrivals_per_replica.get(r, 0) + 1

    def note_completion(self, replica: int) -> None:
        """Count one successfully finished request against the replica
        that first homed it (profile.replica_id — stable across
        failovers, so arrivals and completions reconcile per home)."""
        r = int(replica)
        self.completions_per_replica[r] = (
            self.completions_per_replica.get(r, 0) + 1
        )

    def note_queue_delay_s(self, delay_s: float) -> None:
        """Record one admission-time queue-delay estimate (bounded
        reservoir). Pre-envelope/cold-replica placements report 0.0 —
        a real sample ("no estimated wait"), kept, not dropped: the
        percentiles must reflect what admission actually saw."""
        s = self.queue_delay_s_samples
        s.append(max(0.0, float(delay_s)))
        if len(s) > _STEP_MS_CAP:
            del s[: len(s) - _STEP_MS_CAP]

    def note_rpc_rtt_ms(self, replica: int, ms: float) -> None:
        """Record one step-RPC round-trip sample for ``replica``
        (bounded per-replica reservoir)."""
        s = self.rpc_rtt_ms_samples.setdefault(int(replica), [])
        s.append(float(ms))
        if len(s) > _STEP_MS_CAP:
            del s[: len(s) - _STEP_MS_CAP]

    @staticmethod
    def _pct(samples: Sequence[float], q: float) -> float:
        if not samples:
            return 0.0
        ordered = sorted(samples)
        idx = min(len(ordered) - 1, int(round(q * (len(ordered) - 1))))
        return ordered[idx]

    @property
    def cluster_step_ms_p50(self) -> float:
        return self._pct(self.cluster_step_ms_samples, 0.50)

    @property
    def cluster_step_ms_p99(self) -> float:
        return self._pct(self.cluster_step_ms_samples, 0.99)

    @property
    def queue_delay_s_p50(self) -> float:
        return self._pct(self.queue_delay_s_samples, 0.50)

    @property
    def queue_delay_s_p99(self) -> float:
        return self._pct(self.queue_delay_s_samples, 0.99)

    def arrivals_completions_per_replica(self) -> Dict[int, Dict[str, int]]:
        """Per-replica arrival/completion reconciliation map — the
        difference is the replica's live (or lost-to-error) load."""
        out: Dict[int, Dict[str, int]] = {}
        for idx in sorted(
            set(self.arrivals_per_replica) | set(self.completions_per_replica)
        ):
            out[idx] = {
                "arrivals": self.arrivals_per_replica.get(idx, 0),
                "completions": self.completions_per_replica.get(idx, 0),
            }
        return out

    def _all_rtt(self) -> List[float]:
        return [
            ms for s in self.rpc_rtt_ms_samples.values() for ms in s
        ]

    @property
    def rpc_rtt_ms_p50(self) -> float:
        return self._pct(self._all_rtt(), 0.50)

    @property
    def rpc_rtt_ms_p99(self) -> float:
        return self._pct(self._all_rtt(), 0.99)

    def rpc_rtt_ms_per_replica(self) -> Dict[int, Dict[str, float]]:
        """Per-replica RTT p50/p99 over the bounded reservoirs."""
        return {
            idx: {
                "p50": round(self._pct(s, 0.50), 3),
                "p99": round(self._pct(s, 0.99), 3),
            }
            for idx, s in sorted(self.rpc_rtt_ms_samples.items())
        }

    def snapshot(
        self, replicas: Sequence["SchedulerStats"] = ()
    ) -> Dict[str, object]:
        """Router counters + the SUM over every replica's scheduler
        counters (numeric fields only; per-replica snapshots ride along
        under ``per_replica`` so nothing is averaged away)."""
        per = [r.snapshot() for r in replicas]
        agg: Dict[str, float] = {}
        for snap in per:
            for k, v in snap.items():
                if isinstance(v, (int, float)):
                    agg[k] = agg.get(k, 0) + v
        # rates do not sum — recompute them over the summed counters
        if per:
            hits = agg.get("prefix_hits", 0)
            misses = agg.get("prefix_misses", 0)
            agg["prefix_hit_rate"] = round(
                hits / (hits + misses), 4
            ) if hits + misses else 0.0
            hit_toks = agg.get("prefix_hit_tokens", 0)
            agg["host_hit_rate"] = round(
                agg.get("host_hit_tokens", 0) / hit_toks, 4
            ) if hit_toks else 0.0
            drafted = agg.get("spec_drafted", 0)
            agg["spec_accept_rate"] = round(
                agg.get("spec_accepted", 0) / drafted, 4
            ) if drafted else 0.0
            # remote replicas mirror their stats from heartbeats — a
            # snapshot taken before the first envelope is empty
            agg["mean_occupancy"] = round(
                sum(s.get("mean_occupancy", 0.0) for s in per) / len(per), 4
            )
            agg["mean_budget_fill"] = round(
                sum(s.get("mean_budget_fill", 0.0) for s in per) / len(per),
                4,
            )
        return {
            "submitted": self.submitted,
            "placements": dict(self.placements),
            "affinity_hits": self.affinity_hits,
            "sheds": self.sheds,
            "migrations": self.migrations,
            "migrated_pages": self.migrated_pages,
            "migrated_bytes": self.migrated_bytes,
            "step_faults": self.step_faults,
            "replica_down": self.replica_down,
            "replica_suspect": self.replica_suspect,
            "probes": self.probes,
            "replica_recoveries": self.replica_recoveries,
            "failovers": self.failovers,
            "retries": self.retries,
            "failover_errors": self.failover_errors,
            "migration_failures": self.migration_failures,
            "migration_queue_depth": self.migration_queue_depth,
            "migration_queue_peak": self.migration_queue_peak,
            "migration_queue_overflows": self.migration_queue_overflows,
            "rpc_errors": self.rpc_errors,
            "rpc_retries": self.rpc_retries,
            "heartbeat_gaps": self.heartbeat_gaps,
            "reconnects": self.reconnects,
            "standby_adoptions": self.standby_adoptions,
            "wire_bytes_sent": self.wire_bytes_sent,
            "wire_bytes_received": self.wire_bytes_received,
            "rpc_inflight_peak": self.rpc_inflight_peak,
            "cluster_step_ms_p50": round(self.cluster_step_ms_p50, 3),
            "cluster_step_ms_p99": round(self.cluster_step_ms_p99, 3),
            "rpc_rtt_ms_p50": round(self.rpc_rtt_ms_p50, 3),
            "rpc_rtt_ms_p99": round(self.rpc_rtt_ms_p99, 3),
            "rpc_rtt_ms_per_replica": self.rpc_rtt_ms_per_replica(),
            "scale_outs": self.scale_outs,
            "scale_ins": self.scale_ins,
            "pool_flips": self.pool_flips,
            "journal_records": self.journal_records,
            "journal_bytes": self.journal_bytes,
            "journal_compactions": self.journal_compactions,
            "manager_recoveries": self.manager_recoveries,
            "journal_replayed": self.journal_replayed,
            "autoscale_decisions": self.autoscale_decisions,
            "retunes": self.retunes,
            "autoscale_predicted_tps": self.autoscale_predicted_tps,
            "autoscale_measured_tps": self.autoscale_measured_tps,
            "queue_delay_s_p50": round(self.queue_delay_s_p50, 6),
            "queue_delay_s_p99": round(self.queue_delay_s_p99, 6),
            "arrivals_completions_per_replica": (
                self.arrivals_completions_per_replica()
            ),
            "replicas": agg,
            "per_replica": per,
        }

    def report(self, replicas: Sequence["SchedulerStats"] = ()) -> str:
        s = self.snapshot(replicas)
        place = " ".join(
            f"{k}={v}" for k, v in sorted(s["placements"].items())
        ) or "none"
        agg = s["replicas"]
        return (
            f"[cluster {len(replicas)} replicas] sub={s['submitted']} "
            f"place[{place}] affinity={s['affinity_hits']} "
            f"shed={s['sheds']} migr={s['migrations']} "
            f"migrB={s['migrated_bytes']} "
            f"faults={s['step_faults']} down={s['replica_down']} "
            f"failover={s['failovers']} migq={s['migration_queue_depth']} "
            f"rpc_err={s['rpc_errors']} rpc_retry={s['rpc_retries']} "
            f"hb_gaps={s['heartbeat_gaps']} reconn={s['reconnects']} "
            f"inflight^={s['rpc_inflight_peak']} "
            f"cstep_ms={s['cluster_step_ms_p50']:.2f}/"
            f"{s['cluster_step_ms_p99']:.2f} "
            f"rtt_ms={s['rpc_rtt_ms_p50']:.2f}/{s['rpc_rtt_ms_p99']:.2f} "
            f"standby={s['standby_adoptions']} "
            f"scale+{s['scale_outs']}/-{s['scale_ins']} "
            f"flip={s['pool_flips']} jrnl={s['journal_records']}r/"
            f"{s['journal_bytes']}B recov={s['manager_recoveries']} "
            f"autoscale={s['autoscale_decisions']}d/{s['retunes']}rt "
            f"qdelay_s={s['queue_delay_s_p50']:.3f}/"
            f"{s['queue_delay_s_p99']:.3f} "
            f"wireB={s['wire_bytes_sent']}/{s['wire_bytes_received']} "
            f"pfx_hit_rate={agg.get('prefix_hit_rate', 0.0)} "
            f"adm={agg.get('admitted', 0)} "
            f"preempt={agg.get('preemptions', 0)} "
            f"retraces={agg.get('retraces', 0)}"
        )


@dataclasses.dataclass
class PerfMetrics:
    """Host-side running aggregate — reference ``PerfMetrics`` future chain
    (``FFModel::update_metrics_task``, reference ``model.cc:3911``)."""

    iterations: int = 0
    totals: Dict[str, float] = dataclasses.field(default_factory=dict)
    loss_total: float = 0.0

    def update(self, loss: float, batch_metrics: Dict[str, float]):
        self.iterations += 1
        self.loss_total += float(loss)
        for k, v in batch_metrics.items():
            self.totals[k] = self.totals.get(k, 0.0) + float(v)

    def averages(self) -> Dict[str, float]:
        if self.iterations == 0:
            return {}
        out = {k: v / self.iterations for k, v in self.totals.items()}
        out["loss"] = self.loss_total / self.iterations
        return out

    def report(self) -> str:
        avg = self.averages()
        parts = [f"{k}={v:.6f}" for k, v in sorted(avg.items())]
        return f"[{self.iterations} iters] " + " ".join(parts)

"""Analytical serving cost model (ROADMAP item 2a).

The training search (``search/unity.py``) already has what the paper
calls the simulator: per-op rooflines + ring-collective formulas from
``search/machine_model.py``. This module is the SERVING counterpart: the same chip model,
priced over the serving-specific kernel regimes the repo actually
ships —

* **decode** is bandwidth-bound weight + KV streaming: every decode
  step reads the full (TP-sharded) weight set once plus every live
  request's KV context (fp / int8 / int4 pages), so step time is
  ``max(flops, bytes)`` through :func:`~..search.machine_model
  .compute_time` with bytes dominating at serving batch sizes, plus
  one program launch a step.
* **prefill** is compute-bound: ``2·params`` FLOPs per uncached prompt
  token (prefix caching removes the cached share), chunked at
  ``prefill_chunk``.
* **TP collectives** go through :class:`~..search.machine_model
  .CollectiveModel` ring formulas over the topology's link degrees —
  two all-reduces of the batch's activations per layer.
* **speculation** multiplies committed tokens per verify step by the
  expected accepted path length (a geometric series in the accept
  rate over the bucket ladder's depth), while the verify step prices
  the whole tree's rows.

Queueing is a deterministic M/D/c-flavored approximation over
Little's-law concurrency — good enough to RANK configurations, which
is all the offline search and the online autoscaler consume. On this
CPU box the absolute numbers are fiction (the chip constants describe
a TPU); predictions are ranked, not absolute, off-chip. :func:`~..search.machine_model
.calibrate_chip` substitutes host-measured constants where absolute
numbers matter.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

from ...search.machine_model import (
    CollectiveModel,
    TPUChip,
    TPUTopology,
    compute_time,
)

__all__ = [
    "ModelGeometry",
    "ServingCandidate",
    "ServingCostModel",
    "ServingPrediction",
    "TrafficProfile",
]

#: Effective KV bytes per stored element by quantization mode, relative
#: to a 2-byte cache dtype: int8 pages carry 1-byte codes + per-page
#: per-KV-head f32 amax scales (measured >=1.9x pages per budget,
#: serve/kv_quant.py), int4 packs two codes per byte (>=3.8x).
_KV_QUANT_BYTES = {None: 2.0, "int8": 1.05, "int4": 0.53}

#: Host-side dispatch overhead per launched program (s): one a decode
#: step, one a prefill chunk.
_DISPATCH_S = 8e-6

#: Dequantization arithmetic per quantized KV byte read (FLOPs): the
#: fused Pallas kernel dequantizes in VMEM nearly for free on a TPU's
#: flops-rich roofline, but on a flops-poor (CPU-calibrated) chip the
#: same term correctly prices quantized pools SLOWER — matching what
#: the XLA fallback path measures off-chip.
_DEQUANT_FLOPS_PER_BYTE = 8.0


@dataclasses.dataclass(frozen=True)
class TrafficProfile:
    """What the cluster is being asked to serve — the cost model's
    second input (fit online by :class:`~.workload.TrafficEstimator`,
    or written down for offline search). Lengths are tokens; the
    arrival rate is requests/second (the estimator converts its
    per-step rate with an explicit step-time, keeping the profile
    itself wall-clock-free)."""

    arrival_rate_rps: float = 1.0
    prompt_len_p50: float = 128.0
    prompt_len_p99: float = 512.0
    output_len_p50: float = 128.0
    output_len_p99: float = 512.0
    #: fraction of prompt tokens served from the prefix cache (hit
    #: tokens / prompt tokens) — removes prefill compute, not KV reads
    prefix_share: float = 0.0
    #: accepted drafted tokens per drafted token (0 = no speculation
    #: signal; the spec pricing treats it as the per-level acceptance)
    spec_accept_rate: float = 0.0
    #: MEASURED drafted-accept rate from a live verify ladder
    #: (SchedulerStats.spec_accept_rate, or the spec_distill eval
    #: harness) — when set, the speculation term prices with this
    #: instead of the ``spec_accept_rate`` prior. None = no measurement.
    measured_accept_rate: Optional[float] = None

    @property
    def prompt_len_mean(self) -> float:
        return 0.7 * self.prompt_len_p50 + 0.3 * self.prompt_len_p99

    @property
    def output_len_mean(self) -> float:
        return 0.7 * self.output_len_p50 + 0.3 * self.output_len_p99


@dataclasses.dataclass(frozen=True)
class ModelGeometry:
    """The model shape the cost model prices — derivable from any
    LLaMA-flavored config object via :meth:`from_model_config`."""

    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    vocab_size: int
    param_bytes: float = 2.0       # bytes per weight (bf16)

    @classmethod
    def from_model_config(cls, cfg: Any) -> "ModelGeometry":
        """Read the standard family config attributes (``hidden_size``,
        ``num_hidden_layers``, ...) — the same duck-typed surface the
        engine itself consumes."""
        return cls(
            hidden_size=int(cfg.hidden_size),
            num_layers=int(cfg.num_hidden_layers),
            num_heads=int(cfg.num_attention_heads),
            num_kv_heads=int(
                getattr(cfg, "num_key_value_heads", None)
                or cfg.num_attention_heads
            ),
            intermediate_size=int(cfg.intermediate_size),
            vocab_size=int(cfg.vocab_size),
        )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def param_count(self) -> float:
        """Dense parameter count: embeddings + per-layer QKV/O + MLP
        (gate/up/down) + the untied LM head."""
        h, kv = self.hidden_size, self.num_kv_heads * self.head_dim
        per_layer = (
            h * h + 2 * h * kv + h * h          # Q, K, V, O
            + 3 * h * self.intermediate_size    # gate, up, down
        )
        return (
            self.num_layers * per_layer + 2 * self.vocab_size * h
        )

    def weight_bytes(self) -> float:
        return self.param_count() * self.param_bytes

    def kv_bytes_per_token(self, kv_quant: Optional[str]) -> float:
        """HBM bytes one token's K+V occupy across all layers."""
        per_elem = _KV_QUANT_BYTES[kv_quant]
        return (
            2.0 * self.num_layers * self.num_kv_heads
            * self.head_dim * per_elem
        )


@dataclasses.dataclass(frozen=True)
class ServingCandidate:
    """One point in the serving search space — the knobs PRs 1–17 left
    hand-tuned. ``to_serving_config`` lowers it to a ready-to-run
    :class:`~..engine.ServingConfig` (TP×PP live outside ServingConfig
    — they are mesh facts the engine derives at build — so the
    candidate carries them alongside)."""

    tp: int = 1
    pp: int = 1
    replicas: int = 1
    page_size: int = 128
    kv_quant: Optional[str] = None
    prefill_replicas: int = 0
    decode_replicas: int = 0
    speculation: bool = False
    #: W×D ladder top rung the speculative arm drafts at
    spec_width: int = 2
    spec_depth: int = 4
    max_requests_per_batch: int = 16
    max_sequence_length: int = 2048
    prefill_chunk: int = 128

    @property
    def chips(self) -> int:
        """Chips the whole candidate occupies."""
        return self.tp * self.pp * self.replicas

    def to_serving_config(self, base: Any = None, **overrides) -> Any:
        """Lower to a :class:`~..engine.ServingConfig` (cluster fields
        validated by the caller running ``validate_cluster`` — the
        search does it before emitting). ``base`` seeds non-searched
        fields (cache dtype, transport, journal, ...)."""
        import dataclasses as _dc

        from ..engine import ServingConfig

        kw = dict(
            max_requests_per_batch=self.max_requests_per_batch,
            max_sequence_length=self.max_sequence_length,
            prefill_chunk=self.prefill_chunk,
            kv_layout="paged",
            page_size=self.page_size,
            kv_quant=self.kv_quant,
            replicas=self.replicas,
            prefill_replicas=self.prefill_replicas,
            decode_replicas=self.decode_replicas,
        )
        kw.update(overrides)
        if base is not None:
            return _dc.replace(base, **kw)
        return ServingConfig(**kw)


@dataclasses.dataclass(frozen=True)
class ServingPrediction:
    """What the cost model predicts for one (candidate, traffic) pair.
    ``tokens_per_s`` is ACHIEVED throughput (offered load capped by
    capacity); ``capacity_tokens_per_s`` is the saturated ceiling —
    both monotone in ``replicas`` by construction."""

    tokens_per_s: float
    capacity_tokens_per_s: float
    ttft_s_p50: float
    ttft_s_p99: float
    tpot_s_p50: float
    tpot_s_p99: float
    queue_delay_s: float
    decode_step_s: float
    #: HBM bytes one chip holds (sharded weights + its KV pool share)
    hbm_bytes_per_chip: float
    hbm_fill: float
    #: pages the (quantization-scaled) pool budget affords per replica
    kv_pages_capacity: int
    #: pages the steady-state working set needs per replica
    kv_pages_needed: int
    page_fill: float
    feasible: bool
    reason: str = ""


class ServingCostModel:
    """Prices :class:`ServingCandidate` × :class:`TrafficProfile` on a
    chip roofline. Stateless between calls — the autoscaler re-predicts
    every evaluation window with the live profile."""

    def __init__(
        self,
        geometry: ModelGeometry,
        chip: Optional[TPUChip] = None,
        topo: Optional[TPUTopology] = None,
    ):
        self.geometry = geometry
        self.chip = chip or TPUChip.v5e()
        self.topo = topo or TPUTopology(chip=self.chip)
        self.collectives = CollectiveModel(self.topo)

    # -- decode ------------------------------------------------------

    def _decode_step_s(
        self,
        cand: ServingCandidate,
        batch: float,
        context_len: float,
        *,
        tree_tokens: float = 1.0,
        oversubscription: float = 1.0,
    ) -> float:
        """One decode (or tree-verify) step's wall time per pipeline
        stage at ``batch`` live rows with ``context_len`` tokens of KV
        each. ``oversubscription > 1`` divides the chip between that
        many co-resident replicas — the CPU-box reality where every
        in-process replica time-slices one device (bench calibrates
        and sets it; dedicated chips leave it at 1)."""
        g = self.geometry
        shards = cand.tp * cand.pp
        rows = batch * tree_tokens
        flops = 2.0 * g.param_count() * rows / shards
        kv_bytes = (
            batch * context_len * g.kv_bytes_per_token(cand.kv_quant)
            / shards
        )
        if cand.kv_quant is not None:
            flops += kv_bytes * _DEQUANT_FLOPS_PER_BYTE
        bytes_moved = g.weight_bytes() / shards + kv_bytes
        chip = self._scaled_chip(oversubscription)
        t = compute_time(chip, flops, bytes_moved)
        # TP collectives: two all-reduces of the rows' activations per
        # layer, through the ring model's link degrees
        if cand.tp > 1:
            ar_bytes = rows * g.hidden_size * g.param_bytes
            t += (g.num_layers / cand.pp) * 2.0 * self.collectives.all_reduce(
                ar_bytes, cand.tp, "model"
            )
        return t + _DISPATCH_S

    def _scaled_chip(self, oversubscription: float) -> TPUChip:
        if oversubscription <= 1.0:
            return self.chip
        return dataclasses.replace(
            self.chip,
            bf16_flops=self.chip.bf16_flops / oversubscription,
            hbm_bandwidth=self.chip.hbm_bandwidth / oversubscription,
        )

    def _spec_commit(self, cand: ServingCandidate,
                     traffic: TrafficProfile) -> Tuple[float, float]:
        """(committed tokens per verify step, tree rows verified). The
        expected accepted path length is the geometric series in the
        per-level accept rate over the ladder's top-rung depth, +1 for
        the verifier's own bonus token."""
        if not cand.speculation:
            return 1.0, 1.0
        rate = traffic.spec_accept_rate
        if traffic.measured_accept_rate is not None:
            # measured verify-ladder acceptance beats the workload prior
            # (serve/spec_distill.py eval harness feeds this)
            rate = traffic.measured_accept_rate
        a = min(max(rate, 0.0), 0.99)
        d = max(1, cand.spec_depth)
        accepted = a * (1.0 - a ** d) / (1.0 - a) if a > 0 else 0.0
        tree = 1.0 + cand.spec_width * cand.spec_depth
        return 1.0 + accepted, tree

    # -- prefill -----------------------------------------------------

    def _prefill_s(
        self,
        cand: ServingCandidate,
        prompt_len: float,
        prefix_share: float,
        *,
        oversubscription: float = 1.0,
    ) -> float:
        """One prompt's prefill wall time: compute-bound 2·params FLOPs
        per UNCACHED token, weight-stream floor, chunk dispatch tax."""
        g = self.geometry
        shards = cand.tp * cand.pp
        uncached = max(1.0, prompt_len * (1.0 - prefix_share))
        flops = 2.0 * g.param_count() * uncached / shards
        bytes_moved = g.weight_bytes() / shards
        chip = self._scaled_chip(oversubscription)
        t = compute_time(chip, flops, bytes_moved)
        if cand.tp > 1:
            ar_bytes = uncached * g.hidden_size * g.param_bytes
            t += (g.num_layers / cand.pp) * 2.0 * self.collectives.all_reduce(
                ar_bytes, cand.tp, "model"
            )
        chunks = math.ceil(uncached / max(1, cand.prefill_chunk))
        t += chunks * _DISPATCH_S
        # pipeline fill: the first token crosses every stage once
        t += (cand.pp - 1) * self.topo.per_hop_latency
        return t

    # -- the prediction ----------------------------------------------

    def predict(
        self,
        cand: ServingCandidate,
        traffic: TrafficProfile,
        *,
        oversubscription: float = 1.0,
    ) -> ServingPrediction:
        """Price one candidate under one traffic profile.

        Concurrency comes from Little's law iterated to a fixed point
        (service time depends on batch, batch on service time — three
        rounds converge well within the model's accuracy); queue wait
        is an M/D/c-flavored closed form that is deterministic, smooth
        and monotone in utilization, which is what the hysteresis
        bands in :mod:`policy` need."""
        g = self.geometry
        slots = cand.max_requests_per_batch
        lam_r = traffic.arrival_rate_rps / max(1, cand.replicas)
        ctx_mean = traffic.prompt_len_mean + 0.5 * traffic.output_len_mean
        commit, tree = self._spec_commit(cand, traffic)

        # Little's-law fixed point for per-replica live batch
        batch = min(float(slots), 1.0)
        t_dec = self._decode_step_s(
            cand, batch, ctx_mean, tree_tokens=tree,
            oversubscription=oversubscription,
        )
        for _ in range(3):
            t_pre = self._prefill_s(
                cand, traffic.prompt_len_mean, traffic.prefix_share,
                oversubscription=oversubscription,
            )
            # per-token latency pays every pipeline stage; per-step
            # throughput overlaps them (dispatch-ahead keeps it full)
            tpot = t_dec * cand.pp / commit
            service = t_pre + traffic.output_len_mean * tpot
            batch = min(float(slots), max(1.0, lam_r * service))
            t_dec = self._decode_step_s(
                cand, batch, ctx_mean, tree_tokens=tree,
                oversubscription=oversubscription,
            )

        # capacity: decode throughput at full slots
        t_dec_full = self._decode_step_s(
            cand, float(slots), ctx_mean, tree_tokens=tree,
            oversubscription=oversubscription,
        )
        cap_per_replica = slots * commit / t_dec_full
        capacity = cap_per_replica * cand.replicas
        offered = traffic.arrival_rate_rps * traffic.output_len_mean
        tokens_per_s = min(offered, capacity)

        # queueing: utilization of the replica's slot pool
        service = t_pre + traffic.output_len_mean * (
            t_dec * cand.pp / commit
        )
        rho = min(lam_r * service / slots, 4.0)
        if rho < 1.0:
            queue = 0.5 * (rho ** 2) / (1.0 - rho) * (service / slots)
        else:
            # saturated: backlog grows — charge the overload linearly
            # so the search/policy still sees a smooth, monotone signal
            queue = service * (1.0 + (rho - 1.0) * slots)

        tpot_p50 = t_dec * cand.pp / commit
        t_dec_p99 = self._decode_step_s(
            cand, min(float(slots), batch + 1),
            traffic.prompt_len_p99 + traffic.output_len_p99,
            tree_tokens=tree, oversubscription=oversubscription,
        )
        tpot_p99 = t_dec_p99 * cand.pp / commit
        ttft_p50 = queue + self._prefill_s(
            cand, traffic.prompt_len_p50, traffic.prefix_share,
            oversubscription=oversubscription,
        )
        ttft_p99 = 3.0 * queue + self._prefill_s(
            cand, traffic.prompt_len_p99, traffic.prefix_share,
            oversubscription=oversubscription,
        )

        # memory: sharded weights + the page pool. The budget keeps the
        # kv_quant invariant: max_cached_tokens means "this much KV HBM"
        # at the FP dtype, so quantized pages multiply the page count.
        budget_tokens = slots * cand.max_sequence_length
        budget_bytes = budget_tokens * g.kv_bytes_per_token(None)
        page_bytes = cand.page_size * g.kv_bytes_per_token(cand.kv_quant)
        pages_capacity = int(budget_bytes // max(1.0, page_bytes))
        # working set: live contexts rounded UP to whole pages (+ half
        # a page of rounding waste per request)
        pages_needed = int(math.ceil(
            batch * (ctx_mean / cand.page_size + 0.5)
        ))
        page_fill = pages_needed / max(1, pages_capacity)
        hbm = (
            g.weight_bytes() / (cand.tp * cand.pp)
            + min(budget_bytes, pages_needed * page_bytes)
            / (cand.tp * cand.pp)
        )
        hbm_fill = hbm / self.chip.hbm_capacity

        feasible, reason = True, ""
        if hbm_fill > 0.95:
            feasible, reason = False, (
                f"weights+KV need {hbm / 1e9:.2f} GB/chip "
                f"({hbm_fill:.0%} of {self.chip.name} HBM)"
            )
        elif rho >= 1.0:
            feasible, reason = False, (
                f"saturated: utilization {rho:.2f} at "
                f"{cand.replicas} replica(s)"
            )
        return ServingPrediction(
            tokens_per_s=tokens_per_s,
            capacity_tokens_per_s=capacity,
            ttft_s_p50=ttft_p50,
            ttft_s_p99=ttft_p99,
            tpot_s_p50=tpot_p50,
            tpot_s_p99=tpot_p99,
            queue_delay_s=queue,
            decode_step_s=t_dec,
            hbm_bytes_per_chip=hbm,
            hbm_fill=hbm_fill,
            kv_pages_capacity=pages_capacity,
            kv_pages_needed=pages_needed,
            page_fill=page_fill,
            feasible=feasible,
            reason=reason,
        )

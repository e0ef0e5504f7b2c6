"""Offline ServingConfig search (ROADMAP item 2a).

The serving twin of ``search/unity.py``: enumerate the candidate space
with hard pruning (chip budget, HBM feasibility), score every survivor
through the analytical cost model, pick by feasible-beats-infeasible
keying, then coordinate-descent refine the winner — re-optimizing one
axis at a time holding the rest (the backtracking flavor unity uses
where axes interact: TP trades against replicas under a chip budget,
page_size against kv_quant under a page budget, speculation against
batch under the verify tax). The emitted candidate lowers to a
ready-to-run ServingConfig that ``validate_cluster`` accepts —
asserted by the search itself before returning, the same
fail-before-emit discipline the engine applies at construction.

SLOs are CONSTRAINTS, not weights: a candidate whose predicted TTFT/
TPOT p99 breaches the SLO is infeasible however fast it is, exactly
like unity's memory-budget λ treatment. Predicted-vs-measured on the
chip is ROADMAP A12's: no run has checked it yet.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from .cost_model import (
    ModelGeometry,
    ServingCandidate,
    ServingCostModel,
    ServingPrediction,
    TrafficProfile,
)

__all__ = ["ServingSearchReport", "search_serving_config"]


@dataclasses.dataclass
class ServingSearchReport:
    """What the search did — mirrors unity's SearchReport shape."""

    evaluated: int = 0
    pruned: int = 0
    refined_moves: int = 0
    best: Optional[ServingCandidate] = None
    prediction: Optional[ServingPrediction] = None
    #: (candidate, prediction) leaderboard, best first, for bench tables
    table: List[Tuple[ServingCandidate, ServingPrediction]] = (
        dataclasses.field(default_factory=list)
    )

    def summary(self) -> str:
        if self.best is None:
            return "serving search: no feasible candidate"
        p = self.prediction
        return (
            f"serving search: {self.evaluated} evaluated / "
            f"{self.pruned} pruned / {self.refined_moves} refine moves — "
            f"best tp={self.best.tp} pp={self.best.pp} "
            f"replicas={self.best.replicas} page={self.best.page_size} "
            f"kv={self.best.kv_quant or 'fp'} "
            f"spec={'on' if self.best.speculation else 'off'} "
            f"→ {p.tokens_per_s:.0f} tok/s "
            f"(ttft_p99={p.ttft_s_p99 * 1e3:.1f} ms, "
            f"tpot_p99={p.tpot_s_p99 * 1e3:.2f} ms)"
        )


def _pow2s(limit: int) -> List[int]:
    out, v = [], 1
    while v <= limit:
        out.append(v)
        v *= 2
    return out


def _slo_ok(pred: ServingPrediction, slo_ttft_s: Optional[float],
            slo_tpot_s: Optional[float]) -> bool:
    if slo_ttft_s is not None and pred.ttft_s_p99 > slo_ttft_s:
        return False
    if slo_tpot_s is not None and pred.tpot_s_p99 > slo_tpot_s:
        return False
    return True


def _key(pred: ServingPrediction, slo_ttft_s, slo_tpot_s):
    """Feasible-beats-infeasible, then throughput (higher better),
    then latency as the tie-break — unity's keying transposed to a
    maximization."""
    ok = pred.feasible and _slo_ok(pred, slo_ttft_s, slo_tpot_s)
    return (not ok, -pred.tokens_per_s, pred.ttft_s_p99)


def search_serving_config(
    geometry: ModelGeometry,
    traffic: TrafficProfile,
    *,
    chip_budget: int = 8,
    slo_ttft_s: Optional[float] = None,
    slo_tpot_s: Optional[float] = None,
    cost_model: Optional[ServingCostModel] = None,
    max_requests_per_batch: int = 16,
    max_sequence_length: int = 2048,
    allow_disagg: bool = True,
    top_k: int = 8,
) -> Tuple[Optional[ServingCandidate], ServingSearchReport]:
    """Search the serving shape space for ``geometry`` under
    ``traffic``, maximizing predicted tokens/sec subject to the SLOs,
    over at most ``chip_budget`` chips. Returns ``(best, report)`` —
    ``best`` is None only when nothing fits (report says why via the
    leaderboard's infeasibility reasons)."""
    cm = cost_model or ServingCostModel(geometry)
    report = ServingSearchReport()
    scored: List[Tuple[ServingCandidate, ServingPrediction]] = []

    # ---- phase 1: pruned enumeration --------------------------------
    weight_gb = geometry.weight_bytes() / cm.chip.hbm_capacity
    for tp in _pow2s(chip_budget):
        # hard prune: sharded weights alone must leave KV headroom
        if weight_gb / tp > 0.9:
            report.pruned += 1
            continue
        for pp in _pow2s(chip_budget // tp):
            for replicas in range(1, chip_budget // (tp * pp) + 1):
                for page_size in (16, 64, 128, 256):
                    for kv_quant in (None, "int8", "int4"):
                        for spec in (
                            (False, True) if traffic.spec_accept_rate > 0
                            else (False,)
                        ):
                            splits = [(0, 0)]
                            if allow_disagg and replicas >= 3:
                                splits.append((1, replicas - 1))
                            for pf, dc in splits:
                                if spec and pf:
                                    # SpecInfer × disagg pools is
                                    # rejected by validate_cluster —
                                    # never emit it
                                    report.pruned += 1
                                    continue
                                cand = ServingCandidate(
                                    tp=tp, pp=pp, replicas=replicas,
                                    page_size=page_size,
                                    kv_quant=kv_quant,
                                    prefill_replicas=pf,
                                    decode_replicas=dc,
                                    speculation=spec,
                                    max_requests_per_batch=(
                                        max_requests_per_batch
                                    ),
                                    max_sequence_length=(
                                        max_sequence_length
                                    ),
                                )
                                pred = cm.predict(cand, traffic)
                                report.evaluated += 1
                                scored.append((cand, pred))

    if not scored:
        return None, report
    scored.sort(key=lambda cp: _key(cp[1], slo_ttft_s, slo_tpot_s))
    report.table = scored[:top_k]
    best, best_pred = scored[0]
    if _key(best_pred, slo_ttft_s, slo_tpot_s)[0]:
        # even the leader is infeasible — report it, emit nothing
        report.best, report.prediction = None, best_pred
        return None, report

    # ---- phase 2: coordinate-descent refinement of the winner -------
    # (the unity backtracking flavor: one axis at a time, keep a move
    # only if it strictly improves the key, loop until a full sweep
    # makes no move)
    axes = ("tp", "pp", "replicas", "page_size", "kv_quant", "speculation")
    moved = True
    while moved:
        moved = False
        for axis in axes:
            for value in _axis_values(axis, best, chip_budget, traffic):
                cand = dataclasses.replace(best, **{axis: value})
                if cand.chips > chip_budget:
                    continue
                pred = cm.predict(cand, traffic)
                report.evaluated += 1
                if (_key(pred, slo_ttft_s, slo_tpot_s)
                        < _key(best_pred, slo_ttft_s, slo_tpot_s)):
                    best, best_pred = cand, pred
                    report.refined_moves += 1
                    moved = True

    # fail-before-emit: the winning candidate must lower to a config
    # the cluster will actually accept
    best.to_serving_config().validate_cluster()
    report.best, report.prediction = best, best_pred
    return best, report


def _axis_values(axis: str, cur: ServingCandidate, chip_budget: int,
                 traffic: TrafficProfile):
    if axis == "tp":
        return [v for v in _pow2s(chip_budget) if v != cur.tp]
    if axis == "pp":
        return [v for v in _pow2s(chip_budget) if v != cur.pp]
    if axis == "replicas":
        vals = {max(1, cur.replicas - 1), cur.replicas + 1}
        return [v for v in sorted(vals) if v != cur.replicas]
    if axis == "page_size":
        return [v for v in (16, 64, 128, 256) if v != cur.page_size]
    if axis == "kv_quant":
        return [v for v in (None, "int8", "int4") if v != cur.kv_quant]
    if axis == "speculation":
        if traffic.spec_accept_rate <= 0 or cur.prefill_replicas:
            return []
        return [not cur.speculation]
    return []

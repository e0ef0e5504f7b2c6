"""The live autoscaler (ROADMAP item 2b): cost-model-driven, journaled,
step-clocked.

One :class:`Autoscaler` rides the ClusterManager's drive loop —
``ClusterManager.step`` calls :meth:`Autoscaler.on_step` once per
cluster step, after replicas stepped and retirements settled but
BEFORE the step's journal sync, so a decision's records batch into the
same durable flush as the step that produced them. Every step it feeds
one telemetry observation to the :class:`~.workload.TrafficEstimator`;
every ``eval_interval_steps`` it runs the fitted profile through the
:class:`~.cost_model.ServingCostModel` and compares predictions
against the config's SLOs:

* **scale_out** when the predicted queue delay / TTFT p99 breaches the
  SLO for ``breach_evals`` consecutive evaluations — capacity is added
  through the PR-14 journaled :func:`~..cluster.reconfigure.scale_out`
  (begin → commit, so a SIGKILL mid-event recovers: an uncommitted
  begin replays as "never happened", a committed one rebuilds the
  grown membership).
* **scale_in** when the one-smaller cluster is predicted to hold the
  SLO with margin (``low_band``) for ``clear_evals`` consecutive
  evaluations — drain-based (:func:`begin_scale_in`; the drive loop's
  ``maybe_retire`` finishes it), never a kill.
* **set_pools** on a disaggregated cluster when the prefill/decode
  backlog ratio leaves its band — re-splits the pools one replica at a
  time.
* **retune** when the live speculation accept rate has drifted across
  a bucket-ladder rung boundary: the decision journals the advised W×D
  rung. The per-request TreeControllers (PR 10) already shape trees
  from their own acceptance — the cluster-level retune is the
  AUDITABLE record of where the fleet-wide ladder should sit, consumed
  by operators and the offline search's next run.

Hysteresis is two one-sided streak counters (breach vs clear) with a
dead band between ``low_band``·SLO and the SLO itself — inside the
band the policy holds. Cooldown windows and streaks are counted in
CLUSTER STEPS, never wall clock: replaying the same telemetry replays
the same decisions. ``dry_run`` (ServingConfig ``autoscale="advise"``)
evaluates, journals and counts every decision but applies none.

Every decision — applied or advisory — increments
``ClusterStats.autoscale_decisions``, journals an ``"autoscale"``
record (replay-ignored: the scale ops' own begin/commit records carry
the recoverable state), and refreshes the predicted-vs-measured
gauges (``autoscale_predicted_tps`` / ``autoscale_measured_tps``) the
Prometheus exporter scrapes.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, List, Optional

from .cost_model import ModelGeometry, ServingCandidate, ServingCostModel
from .workload import TrafficEstimator

__all__ = ["AutoscaleDecision", "Autoscaler"]

_log = logging.getLogger("flexflow.serve.autotune")


@dataclasses.dataclass
class AutoscaleDecision:
    """One policy decision, journaled and kept on
    ``Autoscaler.decisions`` for tests/bench to read back."""

    step: int
    kind: str            # "scale_out" | "scale_in" | "set_pools" | "retune"
    reason: str
    applied: bool
    detail: Dict[str, Any] = dataclasses.field(default_factory=dict)


class Autoscaler:
    """Policy loop over one ClusterManager. Construction is cheap and
    device-free; all per-step work is host-side counter arithmetic
    (ffcheck FF107 roots this file's drive-loop surface)."""

    def __init__(
        self,
        cm,
        *,
        cost_model: ServingCostModel,
        estimator: Optional[TrafficEstimator] = None,
        dry_run: bool = False,
        cooldown_steps: int = 64,
        min_replicas: int = 1,
        max_replicas: int = 2,
        eval_interval_steps: int = 8,
        breach_evals: int = 2,
        clear_evals: int = 4,
        low_band: float = 0.5,
        step_time_s: Optional[float] = None,
    ):
        if max_replicas < min_replicas:
            raise ValueError(
                f"max_replicas ({max_replicas}) < min_replicas "
                f"({min_replicas})"
            )
        if cooldown_steps < 1 or eval_interval_steps < 1:
            raise ValueError(
                "cooldown_steps and eval_interval_steps must be >= 1"
            )
        if not 0.0 < low_band < 1.0:
            raise ValueError(f"low_band must be in (0, 1) (got {low_band})")
        self.cm = cm
        self.cost_model = cost_model
        self.estimator = estimator or TrafficEstimator()
        self.dry_run = dry_run
        self.cooldown_steps = cooldown_steps
        self.min_replicas = min_replicas
        self.max_replicas = max_replicas
        self.eval_interval_steps = eval_interval_steps
        self.breach_evals = breach_evals
        self.clear_evals = clear_evals
        self.low_band = low_band
        #: pins the step-time used for rate conversion (tests/bench);
        #: None = the live measured cluster_step_ms p50
        self.step_time_s = step_time_s
        self.decisions: List[AutoscaleDecision] = []
        # hysteresis state — streaks at eval cadence, cooldown armed
        # from the CURRENT step so a freshly recovered manager never
        # fires into a cluster it has not yet observed
        self._breach_streak = 0
        self._clear_streak = 0
        self._last_action_step = int(getattr(cm, "_step_counter", 0))
        self._advised_rung: Optional[int] = None
        self._measured_window: List[int] = []   # tokens completed/step

    # -- construction from a live manager -----------------------------

    @classmethod
    def from_manager(cls, cm) -> "Autoscaler":
        """Build from ``cm.serving``'s autoscale fields + the lead
        replica's model config (the geometry every replica shares)."""
        sc = cm.serving
        ctx = getattr(cm, "_build_ctx", None)
        cfg = ctx["cfg"] if ctx else cm.replicas[0].engine.cfg
        geom = ModelGeometry.from_model_config(cfg)
        return cls(
            cm,
            cost_model=ServingCostModel(geom),
            dry_run=(sc.autoscale == "advise"),
            cooldown_steps=sc.autoscale_cooldown_steps,
            min_replicas=sc.autoscale_min_replicas,
            max_replicas=sc.autoscale_max_replicas,
        )

    # -- the per-step hook --------------------------------------------

    def on_step(self, step_no: int) -> Optional[AutoscaleDecision]:
        """One cluster step: observe always, evaluate at the eval
        cadence. Returns the decision made this step, if any."""
        self.estimator.observe_cluster(self.cm)
        self._measured_window.append(self._completed_tokens_delta())
        if len(self._measured_window) > 256:
            del self._measured_window[:-256]
        if step_no % self.eval_interval_steps != 0:
            return None
        if not self.estimator.ready():
            return None
        return self._evaluate(step_no)

    def _completed_tokens_delta(self) -> int:
        # decode_tokens is cumulative over replicas; delta per step
        total = 0
        for rep in self.cm.replicas:
            try:
                total += int(getattr(rep.stats, "decode_tokens", 0))
            except Exception:
                continue
        prev = getattr(self, "_seen_decode_tokens", 0)
        self._seen_decode_tokens = max(prev, total)
        return max(0, total - prev)

    # -- evaluation ---------------------------------------------------

    def _step_time(self) -> float:
        if self.step_time_s is not None:
            return self.step_time_s
        measured = self.cm.stats.cluster_step_ms_p50 / 1e3
        return measured if measured > 0 else 0.01

    def _candidate(self, replicas: int) -> ServingCandidate:
        sc = self.cm.serving
        pf = sc.prefill_replicas
        return ServingCandidate(
            replicas=replicas,
            page_size=sc.page_size,
            kv_quant=sc.kv_quant,
            prefill_replicas=min(pf, max(0, replicas - 1)) if pf else 0,
            decode_replicas=(
                replicas - min(pf, max(0, replicas - 1)) if pf else 0
            ),
            speculation=self.estimator.spec_accept_rate() > 0,
            max_requests_per_batch=sc.max_requests_per_batch,
            max_sequence_length=sc.max_sequence_length,
            prefill_chunk=sc.prefill_chunk,
        )

    def _slo(self) -> Dict[str, Optional[float]]:
        sc = self.cm.serving
        return {
            "ttft": sc.slo_ttft_s,
            "tpot": sc.slo_tpot_s,
            "queue": sc.slo_queue_delay_s,
        }

    def _breaches(self, pred, slo) -> Optional[str]:
        """Which SLO the prediction breaches, or None."""
        if slo["ttft"] is not None and pred.ttft_s_p99 > slo["ttft"]:
            return (f"predicted ttft_p99 {pred.ttft_s_p99:.3f}s > "
                    f"slo_ttft_s {slo['ttft']}")
        if slo["tpot"] is not None and pred.tpot_s_p99 > slo["tpot"]:
            return (f"predicted tpot_p99 {pred.tpot_s_p99:.4f}s > "
                    f"slo_tpot_s {slo['tpot']}")
        if slo["queue"] is not None and pred.queue_delay_s > slo["queue"]:
            return (f"predicted queue delay {pred.queue_delay_s:.3f}s > "
                    f"slo_queue_delay_s {slo['queue']}")
        return None

    def _clear(self, pred, slo) -> bool:
        """True when the prediction holds EVERY set SLO with the
        hysteresis margin — the scale-in side of the dead band."""
        ok = True
        if slo["ttft"] is not None:
            ok &= pred.ttft_s_p99 <= self.low_band * slo["ttft"]
        if slo["tpot"] is not None:
            ok &= pred.tpot_s_p99 <= self.low_band * slo["tpot"]
        if slo["queue"] is not None:
            ok &= pred.queue_delay_s <= self.low_band * slo["queue"]
        return ok

    def _evaluate(self, step_no: int) -> Optional[AutoscaleDecision]:
        cm = self.cm
        n = len(cm.replicas) - len(getattr(cm, "_draining", ()))
        profile = self.estimator.profile(step_time_s=self._step_time())
        slo = self._slo()
        pred_now = self.cost_model.predict(self._candidate(n), profile)
        # predicted-vs-measured gauges: what the model says the current
        # shape should stream vs what the fleet actually committed
        st = self._step_time()
        window = self._measured_window[-64:]
        measured = (sum(window) / (len(window) * st)) if window else 0.0
        cm.stats.autoscale_predicted_tps = round(pred_now.tokens_per_s, 3)
        cm.stats.autoscale_measured_tps = round(measured, 3)

        breach = self._breaches(pred_now, slo)
        if breach is not None:
            self._breach_streak += 1
            self._clear_streak = 0
        else:
            self._breach_streak = 0
            if n > self.min_replicas:
                pred_smaller = self.cost_model.predict(
                    self._candidate(n - 1), profile
                )
                if pred_smaller.feasible and self._clear(pred_smaller, slo):
                    self._clear_streak += 1
                else:
                    self._clear_streak = 0
            else:
                self._clear_streak = 0

        in_cooldown = (
            step_no - self._last_action_step < self.cooldown_steps
        )
        if not in_cooldown:
            if (self._breach_streak >= self.breach_evals
                    and n < self.max_replicas):
                return self._decide_scale_out(step_no, breach, pred_now)
            if (self._clear_streak >= self.clear_evals
                    and n > self.min_replicas):
                return self._decide_scale_in(step_no, pred_now)
            d = self._maybe_retune(step_no)
            if d is not None:
                return d
        return None

    # -- decisions ----------------------------------------------------

    def _record(self, dec: AutoscaleDecision) -> AutoscaleDecision:
        cm = self.cm
        cm.stats.autoscale_decisions += 1
        self.decisions.append(dec)
        if cm.journal is not None:
            # the decision record is the audit trail; the applied ops'
            # own reconfig begin/commit records (written by scale_out /
            # begin_scale_in / set_pools) carry the recoverable state
            cm.journal.append({
                "type": "autoscale", "step": dec.step, "kind": dec.kind,
                "applied": dec.applied, "reason": dec.reason,
                **{k: v for k, v in dec.detail.items()
                   if isinstance(v, (int, float, str, bool))},
            })
        self._last_action_step = dec.step
        self._breach_streak = 0
        self._clear_streak = 0
        _log.warning(
            "autoscale[%s]%s @step %d: %s", dec.kind,
            "" if dec.applied else " (advise)", dec.step, dec.reason,
        )
        return dec

    def _decide_scale_out(self, step_no, breach, pred) -> AutoscaleDecision:
        cm = self.cm
        role = "mixed"
        if cm.disaggregated:
            # grow the pool whose SLO is hurting: TTFT lives on the
            # routed prefill pool, TPOT/queue on the decode pool
            role = "prefill" if "ttft" in breach else "decode"
        applied = not self.dry_run
        detail = {"role": role}
        if applied:
            try:
                # journaled begin→commit inside scale_out — the
                # crash-recovery contract lives there, not here
                detail["pos"] = cm.scale_out(role=role)
            except Exception as exc:
                # e.g. a socket cluster with no spare endpoint: the
                # decision downgrades to advisory, the drive loop lives
                applied = False
                breach = f"{breach}; scale_out failed: {exc}"
        dec = AutoscaleDecision(
            step=step_no, kind="scale_out", applied=applied,
            reason=breach, detail=detail,
        )
        return self._record(dec)

    def _scale_in_target(self) -> Optional[int]:
        """The retiree: the LAST-joined routable replica whose pool
        can spare it (reverse join order keeps the original build's
        replicas stable — the bench's zero-recompiles-on-untouched
        assertion depends on it)."""
        cm = self.cm
        draining = getattr(cm, "_draining", set())
        for pos in sorted(
            range(len(cm.replicas)),
            key=lambda p: -cm.replicas[p].index,
        ):
            rep = cm.replicas[pos]
            if rep.index in draining or not cm._routable_pos(pos):
                continue
            if cm.disaggregated:
                pool = (cm.prefill_pool if rep.role == "prefill"
                        else cm.decode_pool)
                if len([r for r in pool
                        if r.index not in draining]) <= 1:
                    continue
            return pos
        return None

    def _decide_scale_in(self, step_no, pred) -> Optional[AutoscaleDecision]:
        cm = self.cm
        pos = self._scale_in_target()
        if pos is None:
            return None
        reason = (
            f"predicted SLOs hold at {len(cm.replicas) - 1} replica(s) "
            f"with {self.low_band:.0%} margin "
            f"(queue {pred.queue_delay_s * 1e3:.1f} ms)"
        )
        applied = not self.dry_run
        if applied:
            try:
                cm.begin_scale_in(pos)
            except Exception as exc:
                applied = False
                reason = f"{reason}; begin_scale_in failed: {exc}"
        dec = AutoscaleDecision(
            step=step_no, kind="scale_in", applied=applied,
            reason=reason, detail={"pos": pos,
                                   "index": cm.replicas[pos].index},
        )
        return self._record(dec)

    def _maybe_retune(self, step_no) -> Optional[AutoscaleDecision]:
        """Speculation-bucket retune from the live accept EMA: advise
        the ladder rung the fleet's acceptance earns. Only fires on
        clusters actually speculating (a spec manager on the lead
        replica), and only when the advised rung CHANGES."""
        cm = self.cm
        spec = getattr(cm.replicas[0].rm, "spec", None)
        ladder = getattr(spec, "bucket_ladder", None)
        if not ladder or len(ladder) < 2:
            return None
        a = self.estimator.spec_accept_rate()
        if a <= 0.0:
            return None
        rung = min(len(ladder) - 1, int(round(a * (len(ladder) - 1))))
        if rung == self._advised_rung:
            return None
        self._advised_rung = rung
        w, d = ladder[rung]
        cm.stats.retunes += 1
        dec = AutoscaleDecision(
            step=step_no, kind="retune", applied=not self.dry_run,
            reason=(f"live accept EMA {a:.2f} advises ladder rung "
                    f"{rung} (W={w}, D={d})"),
            detail={"rung": rung, "width": w, "depth": d},
        )
        return self._record(dec)

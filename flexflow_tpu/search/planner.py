"""Mesh-degree planner for stacked-decoder (LLaMA-style) training.

The graph-level Unity search (unity.py) assigns per-op sharding states
over a (data, model[, expert]) grid; pipeline and sequence degrees live
at a different altitude — they restructure the *program* (GPipe
schedule, ring attention), not one op. This planner covers that axis:
it enumerates every (dp, tp, pp, sp) factorization of the device count
for a decoder config and scores it with the scaling-book cost model —
MXU compute, Megatron all-reduces per layer, GPipe bubble + stage
hand-offs, ring-attention K/V rotation, DP gradient all-reduce — under
an HBM-fit constraint (params + optimizer moments + rematerialized
activations). The winner plugs straight into
``transformer.make_train_step``'s MachineSpec.

The reference explores its analogous dims inside one search because
Legion tasks make pipelining just another placement; under XLA the
split mirrors how the programs are actually built (reference fixes
inference PP outside the search too, inference_manager.cc:91).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from ..core.mesh import MachineSpec
from .machine_model import TPUChip, TPUTopology
from .unity import _divisors


@dataclasses.dataclass
class PlanReport:
    spec: MachineSpec
    step_time_s: float
    breakdown: Dict[str, float]
    feasible: bool
    hbm_bytes: float
    candidates: int


def plan_decoder_mesh(
    num_devices: int,
    *,
    num_layers: int,
    hidden: int,
    intermediate: int,
    vocab: int,
    num_heads: int,
    num_kv_heads: Optional[int] = None,
    batch: int,
    seq: int,
    topo: Optional[TPUTopology] = None,
    dtype_bytes: int = 2,
    optimizer_bytes_per_param: int = 12,  # bf16 param + f32 grad+m+v (Adam)
    max_microbatches: int = 32,
) -> PlanReport:
    """Pick (dp, tp, pp, sp) for a decoder train step. Returns the best
    feasible plan (or the least-infeasible one, flagged)."""
    topo = topo or TPUTopology(chip=TPUChip.v5e(), num_chips=num_devices)
    chip = topo.chip
    kv = num_kv_heads or num_heads
    head_dim = hidden // num_heads

    # per-layer parameter count and per-token matmul flops
    layer_params = (
        hidden * num_heads * head_dim        # wq
        + 2 * hidden * kv * head_dim         # wk, wv
        + num_heads * head_dim * hidden      # wo
        + 3 * hidden * intermediate          # w1, w2, w3
    )
    total_params = num_layers * layer_params + 2 * vocab * hidden
    flops_per_token_layer = 2 * layer_params + 4 * hidden * seq  # + attn
    tokens = batch * seq

    ici = chip.ici_bandwidth
    eff_flops = chip.bf16_flops * chip.mxu_efficiency

    best: Optional[PlanReport] = None
    best_any: Optional[PlanReport] = None
    n_cand = 0
    for tp in _divisors(num_devices):
        if num_heads % tp or kv % tp:
            continue
        for pp in _divisors(num_devices // tp):
            if num_layers % pp:
                continue
            for sp in _divisors(num_devices // (tp * pp)):
                dp = num_devices // (tp * pp * sp)
                if batch % dp or (sp > 1 and seq % sp):
                    continue
                if sp > 1 and pp > 1:
                    # make_train_step doesn't compose ring attention
                    # with the GPipe path yet — don't plan what the
                    # executor can't run
                    continue
                n_cand += 1
                mb = max(pp, min(max_microbatches, batch // dp))
                # --- compute (divides over every axis) ---
                t_comp = (
                    3.0 * flops_per_token_layer * num_layers * tokens
                    / num_devices / eff_flops
                )
                # --- Megatron TP all-reduces: ~4/layer (fwd+bwd) ---
                act = batch * seq * hidden * dtype_bytes / (dp * sp)
                t_tp = 0.0
                if tp > 1:
                    ar = 2.0 * act * (tp - 1) / tp / ici
                    t_tp = 4.0 * (num_layers / pp) * ar
                # --- GPipe bubble + stage hand-offs ---
                t_pp = 0.0
                if pp > 1:
                    t_pp = (t_comp + t_tp) * (pp - 1) / mb
                    t_pp += 2.0 * (pp - 1) * (act / mb) / ici
                # --- ring-attention K/V rotation ---
                t_sp = 0.0
                if sp > 1:
                    kv_bytes = (
                        2 * batch * seq * kv * head_dim * dtype_bytes
                        / (dp * sp)
                    )
                    t_sp = (
                        3.0 * (num_layers / pp) * kv_bytes * (sp - 1) / sp / ici
                    )
                # --- DP gradient all-reduce ---
                t_dp = 0.0
                if dp > 1:
                    grad = total_params * dtype_bytes / (tp * pp)
                    t_dp = 2.0 * grad * (dp - 1) / dp / ici
                t = t_comp + t_tp + t_pp + t_sp + t_dp

                # --- HBM fit: params + optimizer + remat activations ---
                hbm = (
                    total_params * optimizer_bytes_per_param / (tp * pp)
                    + 2.0 * batch * seq * hidden * dtype_bytes
                    * (num_layers / pp) / (dp * sp)
                )
                feasible = hbm <= 0.9 * chip.hbm_capacity
                rep = PlanReport(
                    spec=MachineSpec(data=dp, pipe=pp, seq=sp, model=tp),
                    step_time_s=t,
                    breakdown={
                        "compute": t_comp, "tp_comm": t_tp,
                        "pp_bubble": t_pp, "sp_comm": t_sp, "dp_sync": t_dp,
                    },
                    feasible=feasible,
                    hbm_bytes=hbm,
                    candidates=0,
                )
                if feasible and (best is None or t < best.step_time_s):
                    best = rep
                if best_any is None or hbm < best_any.hbm_bytes:
                    best_any = rep
    winner = best or best_any
    if winner is None:
        raise ValueError(
            f"no (dp, tp, pp, sp) factorization of {num_devices} devices "
            f"satisfies the divisibility constraints (layers={num_layers}, "
            f"heads={num_heads}, batch={batch}, seq={seq})"
        )
    winner.candidates = n_cand
    return winner

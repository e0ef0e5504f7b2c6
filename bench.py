"""Benchmark entry point — prints one JSON line PER METRIC, headline last.

Headline metric (BASELINE.json): serving tokens/sec/chip for SpecInfer
on the flagship LLaMA family, measured on the real chip with the Pallas
decode/verify kernels, alongside incremental decoding and the
spec-vs-incremental LLM-step reduction (the comparison the reference's
inference tests print, tests/inference/python_inference_tests.sh:57-123).
Secondary: hand-sharded single-chip training MFU vs the 40% north star,
Unity-searched training MFU (compile(auto_parallel=True)), weight-only
int8/int4 serving, and a true LLaMA-7B-shape int4 serving phase (the
BASELINE.json headline model, inference/models/llama.cc:23 — int4
weights ~3.5 GB fit the single 16 GB chip).

Process contract (a bench that dies mid-run must still leave data):
* the ORCHESTRATOR process never imports jax, so it never holds the
  chip: a chip belongs to one process at a time, and the phase children
  run one after another;
* the backend is probed once in a subprocess. Without a TPU the bench
  FAILS — unless JAX_PLATFORMS=cpu was asked for explicitly, which is a
  correctness smoke whose numbers are never device metrics (a rate
  against the chip's peak is "not measured" there);
* every phase runs in its OWN subprocess under a parent-enforced
  timeout (kills wedged native compiles, which SIGALRM cannot); each
  metric is printed/flushed the moment the child emits it, so a crash
  or timeout later loses only later phases. A phase that fails stays
  failed: nothing is retried on another platform or another kernel
  path;
* the Pallas kernels are used only after an on-device parity phase
  proves they compile AND match the XLA path token-for-token.

Model: the largest LLaMA-family config that comfortably fits one 16 GB
v5e chip in bf16 (~3.5 B params); the 7 B phase uses int4 weights. The
draft model is a layer-skip self-draft (first K layers + shared
embed/head) so the bench needs no external weights; on random weights
it still yields a real step reduction, and with trained weights the
acceptance only improves.

vs_baseline for the headline compares SpecInfer tokens/sec/chip against
an A100 running LLaMA-7B SpecInfer (~60 tok/s/device: the reference
reports 1.3-2.0x over ~30 tok/s incremental serving baselines,
reference SERVE.md:10).
"""
import argparse
import json
import os
import subprocess
import sys
import threading
import time
import traceback

A100_SPECINFER_TOKS_PER_SEC = 60.0
A100_INCR_TOKS_PER_SEC = 30.0
TRAIN_MFU_TARGET = 0.40

_RESULTS = {}


def _log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def emit(metric, value, unit, vs_baseline=None, **detail):
    line = {"metric": metric, "value": value, "unit": unit}
    if vs_baseline is not None:
        line["vs_baseline"] = round(vs_baseline, 4)
    if detail:
        line["detail"] = detail
    print(json.dumps(line), flush=True)
    _RESULTS[metric] = line
    return line


# ----------------------------------------------------------------------
# orchestrator: probe + per-phase subprocesses (never imports jax)


def _probe_backend(timeout=None):
    """Out-of-process backend probe: the platform a fresh child will
    see. No TPU is a failure, not a fallback — the one exception is an
    explicit ``JAX_PLATFORMS=cpu``."""
    timeout = timeout or int(os.environ.get("BENCH_PROBE_TIMEOUT", "120"))
    code = "import jax; print(jax.devices()[0].platform)"
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout,
    )
    plat = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else "?"
    if r.returncode != 0:
        err = r.stderr.strip().splitlines()[-1] if r.stderr.strip() else ""
        sys.exit(f"[bench] backend probe failed (rc={r.returncode}): {err}")
    _log(f"backend probe: platform={plat}")
    if plat != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(f"[bench] no TPU (found {plat!r}) and JAX_PLATFORMS=cpu "
                 "was not asked for — not measuring")
    return plat


def _record_child_line(line):
    """Parse+relay one child stdout line. Metric lines are re-emitted on
    the orchestrator's stdout and recorded for headline selection."""
    try:
        obj = json.loads(line)
        assert isinstance(obj, dict) and "metric" in obj
    except Exception:
        print(line, file=sys.stderr, flush=True)
        return
    print(json.dumps(obj), flush=True)
    _RESULTS[obj["metric"]] = obj


def _run_phase_child(phase, platform, kernels, budget_s):
    """Run one phase in a subprocess, streaming its stdout. Returns the
    child's rc (or -9 on parent-enforced timeout)."""
    cmd = [
        sys.executable, os.path.abspath(__file__),
        "--child", phase, "--platform", platform, "--kernels", kernels,
    ]
    _log(f"phase {phase} [{platform}] start (budget {budget_s}s)")
    t0 = time.monotonic()
    p = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=None, text=True, bufsize=1,
    )

    def reader():
        for raw in p.stdout:
            _record_child_line(raw.rstrip("\n"))

    th = threading.Thread(target=reader, daemon=True)
    th.start()
    try:
        rc = p.wait(timeout=budget_s)
    except subprocess.TimeoutExpired:
        _log(f"phase {phase} [{platform}] exceeded {budget_s}s — killing")
        p.kill()
        p.wait()
        rc = -9
    th.join(5)
    _log(f"phase {phase} [{platform}] rc={rc} in {time.monotonic() - t0:.1f}s")
    return rc


# (phase, tpu_budget_s, cpu_budget_s, needs_kernels, cpu_ok) —
# needs_kernels phases depend on the parity gate's pallas/xla verdict.
# Budgets are deliberately tight-ish: the driver's OUTER timeout is
# unknown, and one wedged phase must not starve the phases behind it —
# the headline-bearing train/parity/serve prefix totals ~37 min worst
# case.
_PHASES = [
    ("train", 420, 300, False, True),
    ("parity", 600, 300, False, True),
    ("serve", 1200, 600, True, True),
    # 64-slot paged-KV serving vs the dense 8-slot ceiling (the
    # reference's 64 request slots, VERDICT.md round 5 missing #3)
    ("serve_paged", 900, 600, True, True),
    # continuous batching under Poisson arrivals at 64 slots vs the
    # flush-on-admit scheduler (tokens/sec/chip + TTFT/TPOT p50/p99)
    ("serve_continuous", 900, 600, True, True),
    # automatic prefix caching on a shared-system-prompt Poisson
    # workload: hit rate + TTFT p50/p99 + tokens/sec/chip, caching on
    # vs off with output parity asserted
    ("serve_prefix", 900, 600, True, True),
    # quantized paged KV (int8 pages, dequant fused into ragged paged
    # attention) vs the fp pool at the SAME max_cached_tokens HBM
    # budget: tokens/sec/chip + TTFT/TPOT p50/p99 + bytes/live-token +
    # slots-before-preemption, output parity asserted
    ("serve_paged_q", 900, 600, True, True),
    # hierarchical KV cache: the int4 packed-nibble rung of the
    # capacity ladder (int4 vs int8 vs bf16 pages-per-budget, >=3.8x
    # asserted) + the host-RAM spill tier A/B (spill vs plain eviction
    # on a 64-slot shared-prefix Poisson workload: TTFT p50/p99,
    # spill/readmit counters, host hit rate, bitwise output parity)
    ("serve_kv_hierarchy", 900, 600, True, True),
    # context-parallel long-context serving: prompt-length ladder
    # (8k/32k/synthetic-100k; CPU runs scale-model lengths) CP-on vs
    # CP-off at the same per-shard budget — bitwise output parity +
    # zero steady-state recompiles asserted, plus the top rung served
    # ONLY under CP (unservable-without-CP asserted)
    ("serve_long_context", 900, 600, True, True),
    # cluster serving: 2 engine replicas behind the front-end router on
    # a shared-prefix Poisson workload — prefix-aware vs round-robin
    # placement (tokens/sec + TTFT p50/p99, hit-rate split, affinity/
    # migration counters), plus a disaggregated 1-prefill/1-decode
    # mini-run (byte-exact page migration); bitwise output parity +
    # zero steady-state recompiles asserted per replica
    ("serve_cluster", 900, 600, True, True),
    # fault-tolerant cluster serving: kill a replica mid-Poisson-run
    # (deterministic FaultPlan) — goodput dip + recovery time, bitwise
    # failed-over outputs vs the fault-free run, zero hung requests,
    # zero steady-state recompiles on survivors asserted
    ("serve_faults", 700, 500, True, True),
    # elastic control plane: Poisson traffic through a live scale
    # 2→3→2 (warm scale_out, drain-based scale_in) plus a scripted
    # manager kill/restart recovered from the durable request journal
    # — zero lost requests + bitwise outputs vs the static-membership
    # run asserted; recovery/drain times + journal bytes/request
    # reported
    ("serve_elastic", 700, 500, True, True),
    # self-driving serving: the measured replicas × kv_quant × spec
    # config ladder vs the serving cost model's predicted capacity
    # (Spearman rank corr >= 0.7 asserted; off-chip the roofline is
    # host-measured, predictions ranked not absolute) + the burst A/B
    # where the live journaled autoscaler drives a full scale_out →
    # drain-based scale_in cycle (bitwise outputs vs the static arm,
    # zero errors, zero steady-state recompiles on the untouched
    # replica, TTFT p99 per arm + recovery steps reported)
    ("serve_autotune", 900, 600, True, True),
    # multi-host cluster transport: loopback-transported replicas
    # (every Replica call through the binary RPC wire codec) with a
    # warm standby — kill the replica holding a set of prefix families
    # and measure warm-standby adoption vs cold re-seed (post-failover
    # prefix hit rate on the adopted families > 0 asserted), plus wire
    # bytes / rpc-retry counters and zero steady-state recompiles on
    # every untripped replica
    ("serve_transport", 700, 500, True, True),
    # concurrent cluster stepping: N=3 loopback replicas behind
    # threaded transports with an injected per-RPC link delay d —
    # serial drive loop (~N·d per cluster step) vs the multiplexed
    # fan-out (~d per step), speedup >= 2.5x asserted with outputs
    # bitwise identical; cluster_step_ms + per-replica RTT percentiles
    # and in-flight depth reported, zero steady-state recompiles
    ("serve_cluster_async", 700, 500, True, True),
    # adaptive speculation: acceptance-driven W×D tree shaping vs the
    # fixed tree (drafted accept rate >=3x asserted) + the early-exit
    # self-draft's tokens/sec vs non-speculative continuous batching
    # (>=1x asserted); bitwise greedy parity + zero steady-state
    # recompiles asserted in both arms
    ("serve_spec_adaptive", 700, 500, True, True),
    # distilled drafts + verify-skip + the megakernel fold: KL-distill
    # a student draft from harvested teacher logits and rank it against
    # layer-skip by measured accept-rate-per-draft-GFLOP (distilled
    # must win per-FLOP); verify-skip A/B on a cold-draft adversarial
    # workload (tokens/sec >= the non-speculative scheduler, bitwise
    # parity, zero steady-state recompiles, skips actually taken);
    # early-exit spec rounds folded into the whole-step walk bitwise
    # the unfused spec arm
    ("serve_spec_distill", 700, 500, True, True),
    # megakernel decode step: per-fusion ablation (rope_kv_write /
    # sampling / both) on small-batch sync decode — decode_step_ms
    # p50/p99 + dispatched programs per step, bitwise parity asserted
    ("serve_fused", 600, 400, True, True),
    # whole-step decode megakernel: PR-6 fused vs whole_step vs
    # whole_step × quantized-allreduce (TP2) — decode_step_ms p50/p99
    # from SchedulerStats, one dispatched program per decode step,
    # strictly fewer launch sites than the per-layer fused step,
    # bitwise parity asserted (CPU runs the interpret-mode walk: the
    # timing rows carry the documented off-chip caveat)
    ("serve_megakernel", 700, 500, True, True),
    ("serve_int8", 600, 400, True, True),
    ("searched", 700, 400, False, True),
    ("serve_int4", 600, 400, True, True),
    # 7B-shape int4: only meaningful on the chip (13.5 GB-of-flops model
    # on the 1-core CPU box would time out without informing anything)
    ("serve_7b", 900, 0, True, False),
]
_NEEDS_KERNELS = {p for p, _, _, nk, _ in _PHASES if nk}


def orchestrate(which):
    platform = _probe_backend()
    kernels = "xla"
    # A single requested serve phase still needs the parity gate first —
    # otherwise it would silently measure the XLA path under the same
    # metric name an --metric all run reports for Pallas.
    wanted = {which} if which != "all" else {p for p, *_ in _PHASES}
    if wanted & _NEEDS_KERNELS:
        wanted.add("parity")
    for phase, tpu_b, cpu_b, needs_kernels, cpu_ok in _PHASES:
        if phase not in wanted:
            continue
        if platform != "tpu" and not cpu_ok:
            _log(f"phase {phase}: skipped (needs TPU)")
            continue
        budget = tpu_b if platform == "tpu" else cpu_b
        _run_phase_child(phase, platform, kernels, budget)
        if phase == "parity":
            # Pallas is enabled only by a parity PASS measured on the
            # platform the serve phases will run on.
            rec = _RESULTS.get("pallas_kernel_parity", {})
            ok = (rec.get("value") == 1.0
                  and (rec.get("detail") or {}).get("platform") == platform)
            kernels = "pallas" if ok else "xla"
            if not ok:
                _log("pallas parity did not pass on the serving platform"
                     " — serve phases run kernels=xla")

    # Derived: the int8-vs-fp uplift on the identical workload (the
    # reference's --8bit-quantization claim, file_loader.cc:651). The
    # bare ratio was misleading off-TPU, so it now carries a
    # platform-appropriate caveat: on the chip decode is
    # HBM-bandwidth-bound and the ratio measures the halved weight
    # read; XLA:CPU decode is compute-bound and pays the dequant as
    # extra FLOPs, so the CPU number routinely reads ~1 or below and
    # says nothing about the TPU claim.
    fp = _RESULTS.get("incr_decode_tokens_per_sec_per_chip")
    q8 = _RESULTS.get("incr_decode_tokens_per_sec_int8")
    if fp and q8 and fp["value"]:
        fp_plat = (fp.get("detail") or {}).get("platform")
        q8_plat = (q8.get("detail") or {}).get("platform")
        if fp_plat == q8_plat:
            caveat = (
                "bandwidth-bound decode on the chip: the ratio measures "
                "the halved per-step weight-read bytes"
                if fp_plat == "tpu" else
                "XLA:CPU decode is compute-bound and pays int8 dequant "
                "as extra FLOPs — treat as a correctness/parity smoke, "
                "not the TPU bandwidth claim"
            )
            emit(
                "int8_speedup_vs_fp",
                round(q8["value"] / fp["value"], 3),
                "ratio",
                platform=fp_plat,
                caveat=caveat,
            )

    # Derived: KV HBM bytes per live token, so BENCH_r*.json tracks
    # memory alongside speed. Chip-measured records outrank CPU ones;
    # the most-quantized pool's figure outranks the rest at equal
    # platform (int4 packed < int8 < fp bytes per line).
    cands = [
        _RESULTS.get(n) for n in (
            "kv_hier_kv_hbm_bytes_per_live_token",
            "paged_q_kv_hbm_bytes_per_live_token",
            "paged_kv_hbm_bytes_per_live_token",
        )
    ]
    cands = [c for c in cands if c]
    if cands:
        rec = next(
            (c for c in cands
             if (c.get("detail") or {}).get("platform") == "tpu"),
            cands[0],
        )
        d = rec.get("detail") or {}
        emit(
            "kv_bytes_per_live_token",
            rec["value"],
            "bytes/token",
            vs_baseline=rec.get("vs_baseline"),
            source=rec["metric"],
            kv_quant=d.get("kv_quant"),
            platform=d.get("platform"),
        )

    # Derived: host-tier effectiveness — the fraction of prefix-cache
    # hit tokens the HOST tier served (re-admitted spilled pages) on
    # the hierarchy phase's churn workload. 0 means the HBM tree alone
    # absorbed the working set (or the tier was off); the counters in
    # the source metric's detail disambiguate.
    rec = _RESULTS.get("kv_hier_serve_tokens_per_sec_per_chip")
    if rec:
        d = rec.get("detail") or {}
        if d.get("host_hit_rate") is not None:
            emit(
                "host_hit_rate",
                d["host_hit_rate"],
                "fraction",
                source=rec["metric"],
                spills=d.get("spills"),
                readmits=d.get("readmits"),
                host_hit_tokens=d.get("host_hit_tokens"),
                platform=d.get("platform"),
            )

    # Derived: long-context TTFT — time to first token of the ladder's
    # TOP rung (the prompt only context parallelism can serve at the
    # configured per-shard budget), in seconds. The CP-off baseline has
    # no figure for this rung by construction (it is asserted
    # unservable there), so the derived metric tracks the latency of
    # the capability itself across rounds.
    rec = _RESULTS.get("long_context_serve_tokens_per_sec_per_chip")
    if rec:
        d = rec.get("detail") or {}
        if d.get("ttft_top_s") is not None:
            emit(
                "long_context_ttft_s",
                d["ttft_top_s"],
                "seconds",
                source=rec["metric"],
                ladder=d.get("ladder"),
                context_shards=d.get("context_shards"),
                per_shard_budget_tokens=d.get("per_shard_budget_tokens"),
                output_parity=d.get("output_parity"),
                platform=d.get("platform"),
            )

    # Derived: cross-replica prefix hit rate — the fraction of cluster
    # admissions served (partly) from SOME replica's radix tree under
    # prefix-aware routing, next to the round-robin rate on the same
    # workload. The gap is the router's contribution: how much cache
    # value placement preserved that spreading the same traffic
    # destroyed.
    rec = _RESULTS.get("cluster_serve_tokens_per_sec_per_chip")
    if rec:
        d = rec.get("detail") or {}
        if d.get("prefix_hit_rate") is not None:
            emit(
                "cluster_prefix_hit_rate",
                d["prefix_hit_rate"],
                "fraction",
                source=rec["metric"],
                round_robin_hit_rate=d.get("rr_prefix_hit_rate"),
                prefix_hit_tokens=d.get("prefix_hit_tokens"),
                rr_prefix_hit_tokens=d.get("rr_prefix_hit_tokens"),
                n_replicas=d.get("n_replicas"),
                migrations=d.get("disagg_migrations"),
                migrated_bytes=d.get("disagg_migrated_bytes"),
                platform=d.get("platform"),
            )

    # Derived: the speculation-efficiency trajectory — drafted accept
    # rate (accepted drafted tokens / drafted tokens; free root/bonus
    # tokens in neither side) so BENCH_r*.json tracks it across rounds.
    # The adaptive controller's rate on its A/B workload outranks the
    # flagship serve phase's fixed-tree rate (same counting, better
    # policy); the fixed figure rides along for the gap.
    rec = _RESULTS.get("spec_adaptive_accept_uplift")
    flag = _RESULTS.get("specinfer_tokens_per_sec_per_chip")
    if rec or flag:
        if rec:
            d = rec.get("detail") or {}
            emit(
                "spec_accept_rate",
                d.get("drafted_accept_rate_adaptive"),
                "fraction",
                source=rec["metric"],
                fixed_tree_rate=d.get("drafted_accept_rate_fixed"),
                accept_uplift=rec["value"],
                tokens_per_verify_step=d.get(
                    "tokens_per_verify_step_adaptive"
                ),
                platform=d.get("platform"),
            )
        else:
            d = flag.get("detail") or {}
            emit(
                "spec_accept_rate",
                d.get("drafted_accept_rate"),
                "fraction",
                source=flag["metric"],
                tokens_per_verify_step=d.get("tokens_per_verify_step"),
                platform=d.get("platform"),
            )

    # Derived: draft utility — measured drafted accept rate per draft
    # GFLOP for the distilled student, next to layer-skip's on the same
    # verify ladder, so BENCH_r*.json tracks whether distillation keeps
    # paying per-FLOP as the recipe and harvest corpus evolve.
    rec = _RESULTS.get("spec_distill_accept_per_gflop")
    if rec:
        d = rec.get("detail") or {}
        emit(
            "accept_rate_per_draft_gflop",
            rec["value"],
            "accept/GFLOP",
            source=rec["metric"],
            layer_skip=d.get("layer_skip_accept_per_gflop"),
            distilled_over_layer_skip=rec.get("vs_baseline"),
            distilled_accept_rate=d.get("distilled_accept_rate"),
            student_geometry=d.get("student_geometry"),
            platform=d.get("platform"),
        )

    # Derived: the verify-skip win — speculative tokens/sec over the
    # non-speculative scheduler on the cold-draft adversarial workload.
    # The strictly-never-worse claim IS this number staying >= 1.
    rec = _RESULTS.get("spec_verify_skip_tokens_per_sec_per_chip")
    if rec:
        d = rec.get("detail") or {}
        emit(
            "verify_skip_win",
            rec.get("vs_baseline"),
            "ratio",
            source=rec["metric"],
            verify_skipped_rounds=d.get("verify_skipped_rounds"),
            spec_reprobes=d.get("spec_reprobes"),
            output_parity=d.get("output_parity"),
            steady_state_recompiles=d.get("steady_state_recompiles"),
            platform=d.get("platform"),
        )

    # Derived: fault-recovery behavior — how long a replica death
    # stalls the requests it stranded (recompute re-admission drain)
    # and how deep the goodput dipped, so BENCH_r*.json tracks the
    # fault-tolerance envelope across rounds.
    rec = _RESULTS.get("faults_serve_tokens_per_sec_per_chip")
    if rec:
        d = rec.get("detail") or {}
        if d.get("recovery_time_s") is not None:
            emit(
                "fault_recovery_time_s",
                d["recovery_time_s"],
                "s",
                source=rec["metric"],
                goodput_dip_ratio=d.get("goodput_dip_ratio"),
                failovers=d.get("failovers"),
                retries=d.get("retries"),
                replica_down=d.get("replica_down"),
                output_parity=d.get("output_parity"),
                platform=d.get("platform"),
            )

    # Derived: control-plane recovery — how long a manager death
    # strands its in-flight requests (journal replay + engine rebuild +
    # recompute re-admission drain), plus the drain cost of a live
    # scale_in and the journal's per-request byte overhead, so
    # BENCH_r*.json tracks the elastic-control-plane envelope the
    # item-2b autoscaler budgets against.
    rec = _RESULTS.get("elastic_serve_tokens_per_sec_per_chip")
    if rec:
        d = rec.get("detail") or {}
        if d.get("manager_recovery_time_s") is not None:
            emit(
                "manager_recovery_time_s",
                d["manager_recovery_time_s"],
                "s",
                source=rec["metric"],
                recover_build_time_s=d.get("recover_build_time_s"),
                drain_time_s=d.get("drain_time_s"),
                journal_bytes_per_request=d.get(
                    "journal_bytes_per_request"),
                journal_replayed=d.get("journal_replayed"),
                lost_requests=d.get("lost_requests"),
                output_parity=d.get("output_parity"),
                platform=d.get("platform"),
            )

    # Derived: cost-model fidelity + autoscaler reaction time — the
    # Spearman rank correlation between the serving cost model's
    # predicted capacity and the measured config ladder (the number
    # the offline search's ordering rests on; off-chip it is a ranked
    # claim, never absolute — the source phase measured the host
    # roofline itself), and the cluster-step span between the live
    # autoscaler's burst scale_out and its post-burst scale_in — so
    # BENCH_r*.json tracks the self-driving envelope across rounds.
    rec = _RESULTS.get("autotune_serve_tokens_per_sec_per_chip")
    if rec:
        d = rec.get("detail") or {}
        if d.get("rank_corr") is not None:
            emit(
                "cost_model_rank_corr",
                d["rank_corr"],
                "spearman",
                source=rec["metric"],
                n_configs=d.get("n_configs"),
                ladder=d.get("ladder"),
                chip_name=d.get("chip_name"),
                search_evaluated=d.get("search_evaluated"),
                platform=d.get("platform"),
            )
        if d.get("autoscale_recovery_steps") is not None:
            emit(
                "autoscale_recovery_steps",
                d["autoscale_recovery_steps"],
                "cluster steps",
                source=rec["metric"],
                scale_outs=d.get("scale_outs"),
                scale_ins=d.get("scale_ins"),
                ttft_p99_static_s=d.get("ttft_p99_static_s"),
                ttft_p99_autoscaled_s=d.get("ttft_p99_autoscaled_s"),
                output_parity=d.get("output_parity"),
                platform=d.get("platform"),
            )

    # Derived: warm-standby adoption value — the post-failover prefix
    # hit rate on the dead replica's families (warm standby vs cold
    # re-seed) plus the transport's wire accounting, so BENCH_r*.json
    # tracks the multi-host failover envelope across rounds.
    rec = _RESULTS.get("transport_standby_warm_hit_rate")
    if rec:
        d = rec.get("detail") or {}
        emit(
            "standby_warm_hit_rate",
            rec["value"],
            "fraction",
            source=rec["metric"],
            cold_reseed_hit_rate=d.get("cold_reseed_hit_rate"),
            standby_adoptions=d.get("standby_adoptions"),
            wire_bytes_sent=d.get("wire_bytes_sent"),
            wire_bytes_received=d.get("wire_bytes_received"),
            rpc_retries=d.get("rpc_retries"),
            rpc_errors=d.get("rpc_errors"),
            output_parity=d.get("output_parity"),
            platform=d.get("platform"),
        )

    # Derived: the cluster step's round-trip cost under concurrent
    # stepping — with N replicas fanned out a step costs ~one RTT, not
    # N — so BENCH_r*.json tracks the O(RTT) drive-loop contract (and
    # the serial baseline it beat) across rounds.
    rec = _RESULTS.get("cluster_async_step_speedup")
    if rec:
        d = rec.get("detail") or {}
        if d.get("concurrent_cluster_step_ms_p50") is not None:
            emit(
                "cluster_step_rtt_ms",
                d["concurrent_cluster_step_ms_p50"],
                "ms",
                vs_baseline=rec.get("vs_baseline"),
                source=rec["metric"],
                serial_cluster_step_ms_p50=d.get(
                    "serial_cluster_step_ms_p50"),
                injected_rpc_delay_ms=d.get("injected_rpc_delay_ms"),
                rpc_rtt_ms_p50=d.get("rpc_rtt_ms_p50"),
                rpc_inflight_peak=d.get("rpc_inflight_peak"),
                replicas=d.get("replicas"),
                output_parity=d.get("output_parity"),
                platform=d.get("platform"),
            )

    # Derived: decode-step latency, so BENCH_r*.json tracks step time
    # across rounds. The serve_fused phase measures it fused AND
    # unfused — the summary carries the fused p50 (the shipped
    # configuration) with the unfused baseline in detail.
    rec = _RESULTS.get("fused_decode_step_ms_p50")
    if rec:
        d = rec.get("detail") or {}
        emit(
            "decode_step_ms_p50",
            rec["value"],
            "ms",
            vs_baseline=rec.get("vs_baseline"),
            source=rec["metric"],
            unfused_decode_step_ms_p50=d.get("base_decode_step_ms_p50"),
            decode_step_ms_p99=d.get("both_decode_step_ms_p99"),
            platform=d.get("platform"),
        )

    # Headline line LAST (the "one JSON line" the driver records):
    # SpecInfer if measured, else the best metric that did land — but a
    # metric measured on the real chip ALWAYS outranks a CPU-retry
    # number, whatever its name (first pass: TPU-only; second: any).
    order = (
        "specinfer_tokens_per_sec_per_chip",
        "incr_decode_tokens_per_sec_per_chip",
        "continuous_serve_tokens_per_sec_per_chip",
        "cluster_serve_tokens_per_sec_per_chip",
        "paged_serve_tokens_per_sec_per_chip",
        "paged_q_serve_tokens_per_sec_per_chip",
        "kv_hier_serve_tokens_per_sec_per_chip",
        "specinfer_tokens_per_sec_7b_int4",
        "incr_decode_tokens_per_sec_int8",
        "unity_searched_train_mfu",
        "llama_train_mfu",
        "pallas_kernel_parity",
    )
    for tpu_only in (True, False):
        for name in order:
            rec = _RESULTS.get(name)
            if rec is None:
                continue
            if tpu_only and (rec.get("detail") or {}).get("platform") != "tpu":
                continue
            print(json.dumps(rec), flush=True)
            return
    # Nothing landed at all — still print a parseable line.
    print(json.dumps({
        "metric": "bench_failed", "value": 0, "unit": "none",
        "vs_baseline": 0,
    }), flush=True)


# ----------------------------------------------------------------------
# model configs (child side)


def _llm_cfg(on_tpu):
    import jax.numpy as jnp

    from flexflow_tpu.models import llama

    if on_tpu:
        return llama.LLaMAConfig(
            vocab_size=32000,
            hidden_size=4096,
            intermediate_size=11008,
            num_hidden_layers=16,
            num_attention_heads=32,
            num_key_value_heads=32,
            max_position_embeddings=2048,
            dtype=jnp.bfloat16,
        )
    return llama.LLaMAConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=344,
        num_hidden_layers=8,
        num_attention_heads=8,
        num_key_value_heads=8,
        max_position_embeddings=256,
        dtype=jnp.float32,
    )


def _llm_cfg_7b():
    """True LLaMA-7B shape (reference inference/models/llama.cc:23)."""
    from flexflow_tpu.models import llama

    return llama.LLaMAConfig.llama_7b()


def _serve_workload(on_tpu):
    """The ONE serving workload the fp and quantized phases all measure —
    shared so their tokens/sec stay apples-to-apples."""
    cfg = _llm_cfg(on_tpu)
    n_new = 48 if on_tpu else 16
    n_req = 4
    prompt_len = 64 if on_tpu else 12
    prompts = [
        [(i * 37 + j * 11 + 3) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_req)
    ]

    def make_sc(kern):
        from flexflow_tpu.serve import ServingConfig

        return ServingConfig(
            max_requests_per_batch=n_req,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=32 if on_tpu else 8,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kern,
        )

    return cfg, prompts, n_new, n_req, make_sc


def _make_rm(model_mod, cfg, params, make_sc, prompts, kernels):
    """Engine + RequestManager, warmed. Returns (rm, kernels): a kernel
    path that fails on the flagship shapes fails the phase — it is not
    swapped for another under the same metric name."""
    from flexflow_tpu.serve import InferenceEngine, RequestManager

    rm = RequestManager(InferenceEngine(model_mod, cfg, params,
                                        make_sc(kernels)))
    rm.generate(prompts, max_new_tokens=4)  # compile
    return rm, kernels


def _layer_skip_draft(cfg, params, k):
    """First-k-layers self-draft (shares embed/norm/head) — no external
    weights needed; LayerSkip-style speculation. Handles quantized
    {"q","scale"} layer leaves (both are stacked along the layer dim)."""
    import dataclasses

    from flexflow_tpu.quantization import is_quantized

    def take(v):
        if is_quantized(v):
            return {"q": v["q"][:k], "scale": v["scale"][:k]}
        return v[:k]

    dcfg = dataclasses.replace(cfg, num_hidden_layers=k)
    dparams = dict(params)
    dparams["layers"] = {n: take(v) for n, v in params["layers"].items()}
    return dcfg, dparams


def _random_quantized_params(cfg, bits, seed=0):
    """Directly materialize a quantized param tree WITHOUT ever holding
    the dense fp weights (a 7B bf16 tree is ~13.5 GB — quantizing it on
    a 16 GB chip would OOM). Layer matmul kernels become random packed
    codes + constant scales; embeddings/norms/head init dense as usual
    from per-leaf shapes. Numerically arbitrary (bench uses random
    weights anyway) but byte- and layout-exact vs quantize_params."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.models import llama
    from flexflow_tpu.quantization import _leaf_names

    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(lambda k: llama.init_params(k, cfg), key)
    qnames = set(_leaf_names({
        n: v for n, v in shapes["layers"].items()
    }))

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(path, sds, k):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        in_layers = any(
            getattr(p, "key", None) == "layers" for p in path[:-1]
        )
        if in_layers and name in qnames:
            L, In, Out = sds.shape
            # generate at the storage dtype directly — an int32 staging
            # array for a 7B leaf is a multi-GB transient this function
            # exists to avoid
            if bits == 8:
                q = jax.random.randint(k, (L, In, Out), -127, 128, jnp.int8)
            else:
                q = jax.random.randint(
                    k, (L, In // 2, Out), 0, 256, jnp.uint8
                )
            scale = jnp.full((L, 1, Out), 0.02 / max(1, In) ** 0.5,
                             jnp.float32)
            return {"q": q, "scale": scale}
        if jnp.issubdtype(sds.dtype, jnp.integer):
            return jnp.zeros(sds.shape, sds.dtype)
        return (jax.random.normal(k, sds.shape, jnp.float32) * 0.02
                ).astype(sds.dtype)

    ks = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef,
        [build(path, sds, k) for (path, sds), k in zip(leaves, ks)],
    )


# ----------------------------------------------------------------------
# phases (each runs in its own child process)


def train_bench(on_tpu):
    """Hand-sharded single-chip training MFU (the r01/r02 metric, kept
    for continuity against the 40% north star). Cheapest phase: one
    compile + 10 steps — runs first so SOME metric always lands."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.core.mesh import MachineSpec, set_mesh as _set_mesh
    from flexflow_tpu.models import llama
    from flexflow_tpu.optimizers import AdamOptimizer

    cfg = llama.LLaMAConfig(
        vocab_size=32000,
        hidden_size=2048,
        intermediate_size=5504,
        num_hidden_layers=16,
        num_attention_heads=16,
        num_key_value_heads=16,
        max_position_embeddings=1024,
        dtype=jnp.bfloat16,
    ) if on_tpu else llama.LLaMAConfig.tiny(dtype=jnp.float32)
    batch, seq = (8, 1024) if on_tpu else (2, 32)
    mesh = MachineSpec().make_mesh(jax.devices()[:1])
    with _set_mesh(mesh):
        init_fn, step, ds = llama.make_train_step(
            cfg, mesh, AdamOptimizer(lr=1e-4), remat=True,
            # save MXU outputs, recompute only elementwise in backward —
            # less recompute than full remat, fits comfortably at this
            # size (llama._remat_policy)
            remat_policy="dots",
            shard_activations=False,
        )
        key = jax.random.PRNGKey(0)
        params, opt_state = init_fn(key)
        tokens = jax.device_put(
            jax.random.randint(key, (batch, seq), 0, cfg.vocab_size, jnp.int32),
            ds,
        )
        params, opt_state, loss = step(params, opt_state, tokens)
        _ = float(loss)  # sync via host fetch
        iters = 10 if on_tpu else 2
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, loss = step(params, opt_state, tokens)
        _ = float(loss)
        dt = (time.perf_counter() - t0) / iters
    tokens_per_step = batch * (seq - 1)
    flops = 3 * llama.flops_per_token(cfg, seq) * tokens_per_step
    # v5e bf16 peak FLOP/s; off the chip there is no peak to divide by
    # and the utilization is not measured
    mfu = flops / dt / 197e12 if on_tpu else None
    emit(
        "llama_train_mfu",
        round(mfu, 4) if on_tpu else "not measured",
        "fraction_of_peak",
        vs_baseline=mfu / TRAIN_MFU_TARGET if on_tpu else None,
        step_ms=round(dt * 1e3, 2),
        tokens_per_sec=round(tokens_per_step / dt, 1),
        model_params_m=round(llama.num_params(cfg) / 1e6, 1),
        platform=_platform(),
    )
    return mfu


def searched_train_bench(on_tpu):
    """Unity-searched training MFU: FFModel.compile(auto_parallel=True)
    on the flagship transformer — the path BASELINE.md's north star #2
    actually specifies. The search must pick the fused-block fast path
    (flash attention + scan + remat) for this to approach 40%."""
    from flexflow_tpu import bench_search

    try:
        res = bench_search.searched_train_mfu(on_tpu)
    except Exception as e:
        if not on_tpu:
            raise
        # a Mosaic/flash failure on flagship shapes must not lose the
        # whole metric — retry the searched path on XLA attention
        _log(f"searched flash path failed, retrying attention=xla: {e!r}")
        traceback.print_exc(file=sys.stderr)
        res = bench_search.searched_train_mfu(
            on_tpu, attention_override="xla"
        )
    emit(
        "unity_searched_train_mfu",
        round(res["mfu"], 4),
        "fraction_of_peak",
        vs_baseline=res["mfu"] / TRAIN_MFU_TARGET,
        platform=_platform(),
        **{k: v for k, v in res.items() if k != "mfu"},
    )
    return res


def kernel_parity(on_tpu):
    """On-device Pallas↔XLA parity: greedy-decode a small model with
    kernels="pallas" and kernels="xla" and require token-identical
    output over prefill + 12 decode steps — the same acceptance
    criterion the reference applies to its hand-written decode kernels
    (tests/inference/python_inference_tests.sh:111-123). Only a PASS
    here lets the serve phase report kernels="pallas"."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import InferenceEngine, RequestManager, ServingConfig

    # Mosaic-friendly small config: head_dim 128 (lane width), few layers.
    cfg = llama.LLaMAConfig(
        vocab_size=2048,
        hidden_size=1024,
        intermediate_size=2816,
        num_hidden_layers=2,
        num_attention_heads=8,
        num_key_value_heads=4,
        max_position_embeddings=256,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    prompts = [[(i * 13 + j * 7 + 1) % cfg.vocab_size for j in range(24)]
               for i in range(2)]
    outs = {}
    for kernels in ("xla", "pallas"):
        sc = ServingConfig(
            max_requests_per_batch=2,
            max_sequence_length=64,
            prefill_chunk=24,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kernels,
        )
        eng = InferenceEngine(llama, cfg, params, sc)
        rm = RequestManager(eng)
        outs[kernels] = [
            o.output_tokens for o in rm.generate(prompts, max_new_tokens=12)
        ]
    match = outs["xla"] == outs["pallas"]
    emit(
        "pallas_kernel_parity",
        1.0 if match else 0.0,
        "bool",
        platform=_platform(),
        # off-TPU the Pallas kernels run interpret=True — a pass there
        # checks semantics, not that Mosaic compiled
        mosaic=on_tpu,
        tokens_xla=outs["xla"][0][:8],
        tokens_pallas=outs["pallas"][0][:8],
    )
    if not match:
        raise AssertionError(
            f"pallas/xla token mismatch: {outs['xla']} vs {outs['pallas']}"
        )
    return True


def serve_bench(on_tpu, kernels):
    """Incremental decoding then SpecInfer on the ~3.5B flagship. The
    LLM engine is shared between the RequestManager and the SpecInfer
    verifier (same params, same cache pool) so the compile bill is one
    engine + one tiny draft, not three engines. Emits the incremental
    number as soon as it is measured — a later spec failure cannot lose
    it."""
    import jax

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import InferenceEngine, SpecConfig, SpecInferManager

    cfg, prompts, n_new, n_req, make_sc = _serve_workload(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rm, kernels = _make_rm(llama, cfg, params, make_sc, prompts, kernels)
    eng = rm.engine

    # --- incremental decoding, steady state (same engine, warmed) ---
    t0 = time.perf_counter()
    outs = rm.generate(prompts, max_new_tokens=n_new)
    incr_dt = time.perf_counter() - t0
    incr_tokens = sum(len(o.output_tokens) for o in outs)
    incr_steps = sum(o.profile.llm_decoding_steps for o in outs)
    incr_tps = incr_tokens / incr_dt
    emit(
        "incr_decode_tokens_per_sec_per_chip",
        round(incr_tps, 2),
        "tokens/sec/chip",
        vs_baseline=incr_tps / A100_INCR_TOKS_PER_SEC,
        kernels=kernels,
        n_requests=n_req,
        new_tokens_per_request=n_new,
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )

    # --- SpecInfer with a layer-skip self-draft; verifier REUSES eng ---
    dcfg, dparams = _layer_skip_draft(cfg, params, 2)
    spec = SpecConfig(beam_width=2, beam_depth=3)
    mgr = SpecInferManager(
        eng,
        InferenceEngine(llama, dcfg, dparams, make_sc(kernels)),
        spec,
    )
    mgr.generate(prompts, max_new_tokens=4)  # warm all spec programs
    t0 = time.perf_counter()
    outs = mgr.generate(prompts, max_new_tokens=n_new)
    spec_dt = time.perf_counter() - t0
    spec_tokens = sum(len(o.output_tokens) for o in outs)
    spec_steps = sum(o.profile.llm_decoding_steps for o in outs)
    accepted = sum(o.profile.accepted_tokens for o in outs)
    speculated = sum(o.profile.speculated_tokens for o in outs)
    spec_tps = spec_tokens / spec_dt
    emit(
        "specinfer_tokens_per_sec_per_chip",
        round(spec_tps, 2),
        "tokens/sec/chip",
        vs_baseline=spec_tps / A100_SPECINFER_TOKS_PER_SEC,
        kernels=kernels,
        spec_step_reduction=round(incr_steps / max(1, spec_steps), 3),
        # honest speculation accounting (two numbers, not one blurred
        # "accept rate"): drafted_accept_rate = accepted DRAFTED tokens
        # over drafted tokens (free root/bonus tokens in neither side —
        # ProfileInfo.speculated_tokens docstring), and the committed
        # output per verify dispatch, which DOES credit the bonus token
        # (that is where the step reduction comes from)
        drafted_accept_rate=round(accepted / max(1, speculated), 3),
        tokens_per_verify_step=round(spec_tokens / max(1, spec_steps), 3),
        incr_tokens_per_sec=round(incr_tps, 2),
        n_requests=n_req,
        new_tokens_per_request=n_new,
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return spec_tps


def _damped_deep_layers(cfg, params, k, scale=0.05):
    """Scale the RESIDUAL-branch output projections (wo, w2) of layers
    >= k by ``scale`` — an early-exit-friendly target whose deep layers
    refine rather than rewrite. Trained checkpoints have exactly that
    redundancy (the LayerSkip premise: late layers mostly sharpen the
    early layers' prediction); random init has NONE of it, so without
    this the early-exit throughput arm would measure draft noise, not
    the controller/verify machinery it exists to measure. The adaptive
    ACCEPT-RATE arm deliberately keeps the raw random weights — a weak
    draft is the regime adaptive shaping is for."""
    import jax.numpy as jnp

    layers = dict(params["layers"])
    for name in ("wo", "w2"):
        w = layers[name]
        layers[name] = jnp.concatenate([w[:k], w[k:] * scale], axis=0)
    out = dict(params)
    out["layers"] = layers
    return out


def serve_spec_adaptive_bench(on_tpu, kernels):
    """Adaptive speculation (ROADMAP item 4): acceptance-driven tree
    shaping + the early-exit self-draft, on the paged pool under the
    continuous-batching scheduler (8 requests into 4 slots — admission
    churn rides the pipelined mixed step, speculation rounds run the
    pure-decode phases).

    Two sub-workloads, each asserting its half of the claim:

    * **accept-rate A/B** (weak 1-layer layer-skip draft on raw random
      weights — the hard-prompt regime): the FIXED tree at the
      reference's own MAX_BEAM_WIDTH=3 / MAX_BEAM_DEPTH=8 defaults
      (batch_config.h:157-161) vs the adaptive controller under the
      same 3x8 bounds on the identical workload. Asserts drafted
      accept rate (accepted drafted / drafted — root/bonus in neither
      side) >= 3x the fixed tree's, bitwise greedy parity vs
      incremental decoding for BOTH arms, zero retraces and zero
      steady-state recompiles (second identical run compiles nothing
      new; one program per W x D bucket by construction).
    * **throughput** (early-exit self-draft on a deep-residual-damped
      target — the trained-model regime, see _damped_deep_layers): the
      SAME engine drafts from its first 2 layers, adaptive controller
      on. Asserts speculative tokens/sec >= the non-speculative
      continuous-batching scheduler on the identical workload, bitwise
      parity, zero steady-state recompiles.

    CPU caveat: XLA:CPU runs steps inline and width-flat, so the wide
    verify dispatch is underpriced relative to the chip and the
    tokens/sec ratio is a parity-grade smoke, not the TPU claim; the
    accept-rate ratio, by contrast, is platform-independent counting.
    """
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import (
        InferenceEngine,
        RequestManager,
        ServingConfig,
        SpecConfig,
        SpecInferManager,
    )

    cfg = llama.LLaMAConfig.tiny(
        dtype=jnp.float32, num_hidden_layers=4, hidden_size=128,
        intermediate_size=256, num_attention_heads=4,
        num_key_value_heads=2, vocab_size=512,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_new = 64
    n_req, slots, prompt_len = 8, 4, 12
    prompts = [
        [(i * 37 + j * 11 + 3) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_req)
    ]

    def make_sc(**kw):
        d = dict(
            max_requests_per_batch=slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=8,
            max_spec_tree_tokens=32,
            cache_dtype=jnp.float32,
            kernels=kernels,
            kv_layout="paged",
            page_size=16,
        )
        d.update(kw)
        return ServingConfig(**d)

    def guards(mgr):
        return [
            g for g in (
                e.retrace_guard for e in [mgr.engine, *mgr.ssms]
            ) if g is not None
        ]

    # ---- accept-rate A/B: fixed 2x4 tree vs adaptive, weak draft ----
    dcfg, dparams = _layer_skip_draft(cfg, params, 1)
    rm = RequestManager(InferenceEngine(llama, cfg, params, make_sc()))
    ref = [o.output_tokens for o in rm.generate(prompts, max_new_tokens=n_new)]

    mgr_fixed = SpecInferManager(
        InferenceEngine(llama, cfg, params, make_sc()),
        InferenceEngine(llama, dcfg, dparams, make_sc()),
        SpecConfig(beam_width=3, beam_depth=8),
    )
    fixed_outs = mgr_fixed.generate(prompts, max_new_tokens=n_new)
    assert [o.output_tokens for o in fixed_outs] == ref, (
        "fixed-tree speculation broke greedy parity"
    )
    fixed_rate = mgr_fixed.stats.spec_accept_rate
    fixed_tpv = sum(len(o.output_tokens) for o in fixed_outs) / max(
        1, sum(o.profile.llm_decoding_steps for o in fixed_outs)
    )

    spec_ad = SpecConfig(beam_width=3, beam_depth=8, adaptive=True)
    mgr_ad = SpecInferManager(
        InferenceEngine(llama, cfg, params, make_sc(sanitizers=("retrace",))),
        InferenceEngine(llama, dcfg, dparams,
                        make_sc(sanitizers=("retrace",))),
        spec_ad,
    )
    ad_outs = mgr_ad.generate(prompts, max_new_tokens=n_new)
    assert [o.output_tokens for o in ad_outs] == ref, (
        "adaptive speculation broke greedy parity"
    )
    compiles_warm = sum(g.total_compiles for g in guards(mgr_ad))
    # steady state: the identical workload again — fresh requests walk
    # the same controller trajectory through the same W x D buckets,
    # so NOTHING may compile (and the strict guard raises on retraces)
    ad_outs2 = mgr_ad.generate(prompts, max_new_tokens=n_new)
    assert [o.output_tokens for o in ad_outs2] == ref
    steady_recompiles = (
        sum(g.total_compiles for g in guards(mgr_ad)) - compiles_warm
    )
    assert steady_recompiles == 0, steady_recompiles
    assert all(g.retraces == 0 for g in guards(mgr_ad))
    ad_rate = mgr_ad.stats.spec_accept_rate
    ad_tpv = sum(len(o.output_tokens) for o in ad_outs) / max(
        1, sum(o.profile.llm_decoding_steps for o in ad_outs)
    )
    uplift = ad_rate / max(fixed_rate, 1e-9)
    emit(
        "spec_adaptive_accept_uplift",
        round(uplift, 2),
        "ratio",
        vs_baseline=uplift / 3.0,  # the >=3x target
        drafted_accept_rate_adaptive=round(ad_rate, 4),
        drafted_accept_rate_fixed=round(fixed_rate, 4),
        tokens_per_verify_step_adaptive=round(ad_tpv, 3),
        tokens_per_verify_step_fixed=round(fixed_tpv, 3),
        tree_resizes=mgr_ad.stats.spec_resizes,
        bucket_ladder=str(spec_ad.bucket_ladder),
        output_parity=1,
        steady_state_recompiles=steady_recompiles,
        kernels=kernels,
        platform=_platform(),
    )
    assert uplift >= 3.0, (
        f"adaptive drafted accept rate {ad_rate:.4f} is only "
        f"{uplift:.2f}x the fixed tree's {fixed_rate:.4f} (>=3x required)"
    )

    # ---- throughput: early-exit self-draft vs incremental, both under
    # the continuous-batching scheduler ----
    bparams = _damped_deep_layers(cfg, params, k=1)
    rm_b = RequestManager(InferenceEngine(llama, cfg, bparams, make_sc()))
    rm_b.generate(prompts, max_new_tokens=n_new)  # warm compiles
    t0 = time.perf_counter()
    ref_b = rm_b.generate(prompts, max_new_tokens=n_new)
    incr_dt = time.perf_counter() - t0
    incr_tokens = sum(len(o.output_tokens) for o in ref_b)
    incr_tps = incr_tokens / incr_dt

    mgr_b = SpecInferManager(
        InferenceEngine(llama, cfg, bparams, make_sc(sanitizers=("retrace",))),
        None,
        SpecConfig(beam_width=2, beam_depth=4, adaptive=True,
                   draft="early_exit", draft_layers=1),
    )
    # warm with the IDENTICAL workload: fresh requests repeat the same
    # controller trajectory, so the timed run below must compile NOTHING
    mgr_b.generate(prompts, max_new_tokens=n_new)
    compiles_warm = sum(g.total_compiles for g in guards(mgr_b))
    t0 = time.perf_counter()
    outs_b = mgr_b.generate(prompts, max_new_tokens=n_new)
    spec_dt = time.perf_counter() - t0
    assert [o.output_tokens for o in outs_b] == [
        o.output_tokens for o in ref_b
    ], "early-exit speculation broke greedy parity"
    steady_b = sum(g.total_compiles for g in guards(mgr_b)) - compiles_warm
    assert steady_b == 0, steady_b
    assert all(g.retraces == 0 for g in guards(mgr_b))
    spec_tokens = sum(len(o.output_tokens) for o in outs_b)
    spec_tps = spec_tokens / spec_dt
    emit(
        "spec_adaptive_tokens_per_sec_per_chip",
        round(spec_tps, 2),
        "tokens/sec/chip",
        vs_baseline=spec_tps / incr_tps,
        incr_tokens_per_sec=round(incr_tps, 2),
        drafted_accept_rate=round(mgr_b.stats.spec_accept_rate, 4),
        tokens_per_verify_step=round(
            spec_tokens / max(1, sum(
                o.profile.llm_decoding_steps for o in outs_b
            )), 3,
        ),
        draft="early_exit",
        draft_layers=1,
        mixed_steps=mgr_b.stats.mixed_steps,
        spec_rounds=mgr_b.stats.spec_rounds,
        output_parity=1,
        steady_state_recompiles=steady_b,
        caveat=(
            "CPU smoke: XLA:CPU steps are width-flat so the wide verify "
            "dispatch is underpriced vs the chip; deep residual branches "
            "are damped to emulate the trained-checkpoint redundancy "
            "early-exit drafting exploits (random weights have none)"
        ) if not on_tpu else None,
        kernels=kernels,
        platform=_platform(),
    )
    assert spec_tps >= incr_tps, (
        f"adaptive speculation ({spec_tps:.1f} tok/s) lost to the "
        f"non-speculative continuous-batching scheduler ({incr_tps:.1f})"
    )
    return spec_tps


def serve_spec_distill_bench(on_tpu, kernels):
    """Distilled drafts + verify-skip + the megakernel fold (ROADMAP
    item 4, the PR-20 half): speculation priced by measured
    accept-rate-per-draft-FLOP instead of chosen by prior.

    Three sub-workloads, each asserting its half of the claim:

    * **draft ladder** (distilled vs layer-skip): harvest
      (context, teacher-logits) pairs by offline trace replay of the
      teacher's own greedy outputs, KL-distill a narrow/shallow
      student (`serve/spec_distill.py`), then run BOTH drafts through
      the same adaptive verify ladder and price each with
      `measure_draft_utility`. Asserts the distilled draft beats the
      1-layer layer-skip draft on accept-rate-per-draft-GFLOP — the
      student is both smaller (denominator) and target-shaped
      (numerator), which is the whole distillation thesis.
    * **verify-skip A/B** (cold-draft adversarial workload — the
      regime where speculation loses to its own overhead): a 1-layer
      layer-skip draft over RAW random weights never gets a token
      accepted, so without verify-skip every round pays draft+verify
      for nothing. `SpecConfig(verify_skip=True)` parks those requests
      on the incremental decode path with periodic re-probes. Asserts
      tokens/sec >= the non-speculative continuous-batching scheduler
      (`verify_skip_win` >= 1), bitwise greedy parity, skips actually
      taken (verify_skipped_rounds > 0, re-probes on cadence), zero
      retraces and zero steady-state recompiles.
    * **megakernel fold** (early-exit draft on the damped-deep
      target): the SAME spec workload with `fused_decode=
      ("whole_step",)` — draft (layer-sliced grid) and verify
      (tree-masked all-positions head) dispatch as two programs of the
      ONE persistent whole-step walk. Asserts the folded outputs are
      bitwise the unfused spec arm's (both bitwise incremental), and
      that the fold actually engaged (whole-step tree/speculate step
      keys present).

    CPU caveat: the skip arm's tokens/sec ratio is timing, so off-chip
    it is a parity-grade smoke (skip rounds run the literal incremental
    step, so the arms execute near-identical work); the draft ladder's
    accept-per-GFLOP ranking and both bitwise assertions are
    platform-independent.
    """
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import (
        InferenceEngine,
        RequestManager,
        ServingConfig,
        SpecConfig,
        SpecInferManager,
    )
    from flexflow_tpu.serve import spec_distill as sd

    cfg = llama.LLaMAConfig.tiny(
        dtype=jnp.float32, num_hidden_layers=4, hidden_size=128,
        intermediate_size=256, num_attention_heads=4,
        num_key_value_heads=2, vocab_size=512,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_new = 48
    n_req, slots, prompt_len = 8, 4, 12
    prompts = [
        [(i * 37 + j * 11 + 3) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_req)
    ]

    def make_sc(**kw):
        d = dict(
            max_requests_per_batch=slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=8,
            max_spec_tree_tokens=32,
            cache_dtype=jnp.float32,
            kernels=kernels,
            kv_layout="paged",
            page_size=16,
        )
        d.update(kw)
        return ServingConfig(**d)

    def guards(mgr):
        return [
            g for g in (
                e.retrace_guard for e in [mgr.engine, *mgr.ssms]
            ) if g is not None
        ]

    # ---- draft ladder: KL-distilled student vs 1-layer layer-skip,
    # both priced by measured accept-rate-per-draft-GFLOP ----
    rm = RequestManager(InferenceEngine(llama, cfg, params, make_sc()))
    traces = rm.generate(prompts, max_new_tokens=n_new)
    ref = [o.output_tokens for o in traces]

    buf = sd.harvest_offline(llama, cfg, params, traces, max_len=48)
    # Low temperature sharpens the teacher targets toward its argmax —
    # the greedy ladder accepts on argmax agreement, and this raw-init
    # teacher's logits are near-uniform (a trained teacher needs less).
    dcfg = sd.DistillConfig(
        hidden_size=64, num_layers=2, num_heads=4,
        seq_len=48, batch_size=8, steps=1500, lr=3e-3,
        temperature=0.02, seed=0,
    )
    scfg, sparams, history = sd.train_distilled_draft(
        buf, cfg, dcfg, family=llama
    )

    def make_mgr(draft_cfg, draft_params, spec):
        return SpecInferManager(
            InferenceEngine(llama, cfg, params, make_sc()),
            InferenceEngine(llama, draft_cfg, draft_params, make_sc()),
            spec,
        )

    ladder = SpecConfig(beam_width=3, beam_depth=8, adaptive=True)
    ev_distilled = sd.measure_draft_utility(
        make_mgr(scfg, sparams, ladder), prompts,
        max_new_tokens=n_new, name="distilled",
    )
    lcfg, lparams = _layer_skip_draft(cfg, params, 1)
    ev_skip = sd.measure_draft_utility(
        make_mgr(lcfg, lparams, ladder), prompts,
        max_new_tokens=n_new, name="layer_skip",
    )
    per_gflop_ratio = ev_distilled.accept_rate_per_gflop / max(
        ev_skip.accept_rate_per_gflop, 1e-9
    )
    emit(
        "spec_distill_accept_per_gflop",
        round(ev_distilled.accept_rate_per_gflop, 2),
        "accept/GFLOP",
        vs_baseline=per_gflop_ratio,  # vs layer-skip; the bar is > 1
        layer_skip_accept_per_gflop=round(ev_skip.accept_rate_per_gflop, 2),
        distilled_accept_rate=round(ev_distilled.accept_rate, 4),
        layer_skip_accept_rate=round(ev_skip.accept_rate, 4),
        distilled_gflops_per_token=round(
            ev_distilled.draft_gflops_per_token, 6),
        layer_skip_gflops_per_token=round(ev_skip.draft_gflops_per_token, 6),
        harvested_examples=len(buf),
        distill_steps=dcfg.steps,
        distill_loss_first=round(history[0], 4),
        distill_loss_last=round(history[-1], 4),
        student_geometry=(
            f"{dcfg.num_layers}L/{dcfg.hidden_size}h/{dcfg.num_heads}H"
        ),
        kernels=kernels,
        platform=_platform(),
    )
    assert per_gflop_ratio > 1.0, (
        f"distilled draft ({ev_distilled.accept_rate_per_gflop:.2f} "
        f"accept/GFLOP) did not beat layer-skip "
        f"({ev_skip.accept_rate_per_gflop:.2f}) on "
        f"accept-rate-per-draft-GFLOP"
    )

    # ---- verify-skip A/B: cold draft, spec must never lose ----
    # the adversarial draft: an UNRELATED random init (not even the
    # teacher's first layer) — nothing it drafts is ever accepted, so
    # without verify-skip every round pays draft+verify for zero tokens
    import dataclasses as _dc
    ccfg = _dc.replace(cfg, num_hidden_layers=1)
    cparams = llama.init_params(jax.random.PRNGKey(7), ccfg)
    rm_cold = RequestManager(InferenceEngine(llama, cfg, params, make_sc()))
    rm_cold.generate(prompts, max_new_tokens=n_new)  # warm compiles
    incr_dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        ref_cold = rm_cold.generate(prompts, max_new_tokens=n_new)
        incr_dt = min(incr_dt, time.perf_counter() - t0)
    assert [o.output_tokens for o in ref_cold] == ref
    incr_tokens = sum(len(o.output_tokens) for o in ref_cold)
    incr_tps = incr_tokens / incr_dt

    spec_vs = SpecConfig(
        beam_width=2, beam_depth=3, adaptive=True,
        verify_skip=True, skip_threshold=0.1, reprobe_every=8,
    )
    mgr_vs = SpecInferManager(
        InferenceEngine(llama, cfg, params, make_sc(sanitizers=("retrace",))),
        InferenceEngine(llama, ccfg, cparams,
                        make_sc(sanitizers=("retrace",))),
        spec_vs,
    )
    # warm with the IDENTICAL workload: fresh requests repeat the same
    # skip/re-probe trajectory, so the timed runs must compile NOTHING
    mgr_vs.generate(prompts, max_new_tokens=n_new)
    compiles_warm = sum(g.total_compiles for g in guards(mgr_vs))
    skip_dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        outs_vs = mgr_vs.generate(prompts, max_new_tokens=n_new)
        skip_dt = min(skip_dt, time.perf_counter() - t0)
    assert [o.output_tokens for o in outs_vs] == ref, (
        "verify-skip broke greedy parity vs incremental decoding"
    )
    steady_vs = sum(g.total_compiles for g in guards(mgr_vs)) - compiles_warm
    assert steady_vs == 0, steady_vs
    assert all(g.retraces == 0 for g in guards(mgr_vs))
    st = mgr_vs.stats
    assert st.verify_skipped_rounds > 0, (
        "cold draft never tripped verify-skip — the A/B measured nothing"
    )
    skip_tokens = sum(len(o.output_tokens) for o in outs_vs)
    skip_tps = skip_tokens / skip_dt
    emit(
        "spec_verify_skip_tokens_per_sec_per_chip",
        round(skip_tps, 2),
        "tokens/sec/chip",
        vs_baseline=skip_tps / incr_tps,  # verify_skip_win; bar is >= 1
        incr_tokens_per_sec=round(incr_tps, 2),
        verify_skipped_rounds=st.verify_skipped_rounds,
        spec_reprobes=st.spec_reprobes,
        spec_rounds=st.spec_rounds,
        drafted_accept_rate=round(st.spec_accept_rate, 4),
        skip_threshold=spec_vs.skip_threshold,
        reprobe_every=spec_vs.reprobe_every,
        output_parity=1,
        steady_state_recompiles=steady_vs,
        caveat=(
            "CPU smoke: skip rounds execute the literal incremental "
            "step so both arms do near-identical work off-chip; the "
            "chip is where skipped draft+verify dispatches were the "
            "measurable loss"
        ) if not on_tpu else None,
        kernels=kernels,
        platform=_platform(),
    )
    assert skip_tps >= incr_tps, (
        f"verify-skip ({skip_tps:.1f} tok/s) lost to the "
        f"non-speculative continuous-batching scheduler ({incr_tps:.1f})"
    )

    # ---- megakernel fold: spec round as two dispatches of the ONE
    # persistent whole-step walk, bitwise the unfused spec arm ----
    bparams = _damped_deep_layers(cfg, params, k=1)
    rm_b = RequestManager(InferenceEngine(llama, cfg, bparams, make_sc()))
    ref_b = [
        o.output_tokens for o in rm_b.generate(prompts, max_new_tokens=n_new)
    ]
    spec_ee = SpecConfig(beam_width=2, beam_depth=3,
                         draft="early_exit", draft_layers=1)
    mgr_unf = SpecInferManager(
        InferenceEngine(llama, cfg, bparams, make_sc()), None, spec_ee,
    )
    unf = [
        o.output_tokens
        for o in mgr_unf.generate(prompts, max_new_tokens=n_new)
    ]
    assert unf == ref_b, "unfused spec arm broke greedy parity"
    eng_fold = InferenceEngine(
        llama, cfg, bparams, make_sc(fused_decode=("whole_step",)),
    )
    assert eng_fold.whole_step_spec_on, (
        "whole-step spec fold did not engage on the untiled "
        "single-shard walk"
    )
    mgr_fold = SpecInferManager(eng_fold, None, spec_ee)
    fold = [
        o.output_tokens
        for o in mgr_fold.generate(prompts, max_new_tokens=n_new)
    ]
    assert fold == unf, (
        "megakernel-folded spec rounds are not bitwise the unfused arm"
    )
    fold_keys = [k for k in eng_fold._steps if "whole_step" in str(k)]
    assert any("whole_step_tree" in str(k) for k in fold_keys), fold_keys
    assert any(
        "speculate" in str(k) and "whole_step" in str(k) for k in fold_keys
    ), fold_keys
    emit(
        "spec_megakernel_fold_parity",
        1.0,
        "bool",
        vs_baseline=1.0,
        whole_step_keys=len(fold_keys),
        spec_rounds=mgr_fold.stats.spec_rounds,
        drafted_accept_rate=round(mgr_fold.stats.spec_accept_rate, 4),
        draft="early_exit",
        draft_layers=1,
        kernels=kernels,
        platform=_platform(),
    )
    return skip_tps


def serve_paged_bench(on_tpu, kernels):
    """High-concurrency serving on the paged KV cache: 64 request slots
    (the reference's MAX_NUM_REQUESTS, request_manager.h) vs the dense
    layout at 8 slots — the pre-paging ceiling this repo had ever been
    exercised at (VERDICT.md round 5). Reports tokens/sec/chip at 64
    slots and the measured KV-HBM-bytes-per-live-token (allocated pages,
    not slots × max_len). vs_baseline is paged-64 over dense-8 on the
    SAME platform — the acceptance bar is ≥ 1."""
    import jax

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import InferenceEngine, RequestManager, ServingConfig

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_new = 32 if on_tpu else 8
    prompt_len = 64 if on_tpu else 12
    page_size = 64 if on_tpu else 16
    if not on_tpu and kernels == "pallas":
        # interpret-mode Pallas is a correctness vehicle, not a perf
        # path: its per-(request, page) Python grid dominates a 64-slot
        # CPU run. Only Mosaic-compiled kernels may carry this metric.
        _log("serve_paged: forcing kernels=xla off-TPU (interpret-mode "
             "pallas would dominate the measurement)")
        kernels = "xla"

    def prompts(n):
        return [
            [(i * 37 + j * 11 + 3) % cfg.vocab_size
             for j in range(prompt_len)]
            for i in range(n)
        ]

    def make_sc(n_req, layout, kern):
        return ServingConfig(
            max_requests_per_batch=n_req,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=32 if on_tpu else 8,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kern,
            kv_layout=layout,
            page_size=page_size,
            # live tokens + one page of slack per slot — far below the
            # 64-slot dense worst case, never preempting mid-run
            max_cached_tokens=(
                n_req * (prompt_len + n_new + page_size)
                if layout == "paged" else None
            ),
            # retrace sentinel: a steady-state recompile raises at the
            # offending dispatch instead of silently deflating tps
            sanitizers=("retrace",),
        )

    def timed(rm, n_req):
        rm.generate(prompts(n_req), max_new_tokens=4)  # warm/compile
        best = 0.0
        for _ in range(2):  # best-of-2: host-side noise dominates small runs
            t0 = time.perf_counter()
            outs = rm.generate(prompts(n_req), max_new_tokens=n_new)
            dt = time.perf_counter() - t0
            best = max(best, sum(len(o.output_tokens) for o in outs) / dt)
        return best

    # --- dense ceiling: 8 slots (kernels kept apples-to-apples) ---
    dense_rm, kernels = _make_rm(
        llama, cfg, params,
        lambda k: make_sc(8, "dense", k), prompts(8), kernels,
    )
    dense_tps = timed(dense_rm, 8)
    del dense_rm

    # --- paged: 64 slots on the same model ---
    rm, kernels = _make_rm(
        llama, cfg, params,
        lambda k: make_sc(64, "paged", k), prompts(64), kernels,
    )
    eng = rm.engine

    # measured bytes/live-token: admit all 64, step through prefill,
    # snapshot allocated pages vs live tokens mid-flight
    rids = [rm.register_request(p) for p in prompts(64)]
    for _ in range(4):
        rm.step()
    live_tokens = sum(
        rm.requests[r].n_cached
        for r in rids if rm.requests[r].slot >= 0
    )
    bytes_per_live_token = (
        eng.kv_allocated_bytes() / max(1, live_tokens)
    )
    dense64_equiv = 64 * (eng.serving.cache_len + 1) * eng.kv_bytes_per_line()
    while rm.step():
        pass  # drain before the timed run

    paged_tps = timed(rm, 64)
    # one compile per step key over warmup + both timed runs — the
    # zero-steady-state-recompiles claim, asserted
    eng.retrace_guard.assert_one_compile_per_key()
    emit(
        "paged_kv_hbm_bytes_per_live_token",
        round(bytes_per_live_token, 1),
        "bytes/token",
        # ideal = K+V line bytes; ratio over it is pure paging overhead
        vs_baseline=bytes_per_live_token / eng.kv_bytes_per_line(),
        kv_pool_bytes=eng.kv_cache_bytes(),
        dense_64slot_equiv_bytes=int(dense64_equiv),
        page_size=page_size,
        platform=_platform(),
    )
    emit(
        "paged_serve_tokens_per_sec_per_chip",
        round(paged_tps, 2),
        "tokens/sec/chip",
        vs_baseline=paged_tps / max(1e-9, dense_tps),
        kernels=kernels,
        n_requests=64,
        dense_8slot_tokens_per_sec=round(dense_tps, 2),
        new_tokens_per_request=n_new,
        kv_hbm_bytes_per_live_token=round(bytes_per_live_token, 1),
        jit_compiles=eng.retrace_guard.total_compiles,
        steady_state_recompiles=eng.retrace_guard.retraces,
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return paged_tps


def serve_continuous_bench(on_tpu, kernels):
    """Continuous batching under churn: Poisson arrivals into 64 paged
    request slots, continuous (pipelined mixed-step) scheduler vs the
    flush-on-admit baseline (``continuous_batching=False`` — the prior
    scheduler, which drains the dispatch-ahead pipeline and drops to a
    blocking sync step whenever any request is PREFILLING). Reports
    tokens/sec/chip with TTFT and TPOT p50/p99 for both schedulers;
    vs_baseline is the throughput ratio.

    Measurement caveat (CPU): XLA:CPU executes the step inline in the
    dispatching thread and its GEMMs leave enough multicore slack that
    step cost is nearly width-independent, so the two structural wins —
    dispatch-ahead overlap across admissions, and narrow mixed steps
    that stop charging decode rows the prompt-chunk width — both vanish
    there: the schedulers measure step-for-step equivalent (~1.0x
    throughput; the continuous side still shows lower TPOT, the
    baseline lower TTFT because pipelined tokens surface dispatch_ahead
    flushes late). The CPU run is therefore a parity/latency smoke; the
    throughput claim is an accelerator property. On TPU the phase runs
    narrow mixed steps (max_tokens_per_step=8 vs prefill_chunk=32)
    where both effects are real. Greedy outputs are
    asserted identical across schedulers (the mixed step's logits are
    bitwise-equal to the sync path — tests/test_continuous_batching.py)."""
    import jax

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import InferenceEngine, RequestManager, ServingConfig

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 64
    n_req = 128 if on_tpu else 96
    n_new = 32 if on_tpu else 16
    prompt_len = 64 if on_tpu else 24
    page_size = 64 if on_tpu else 16
    # The baseline (flush-on-admit sync scheduler) runs its natural
    # large-chunk operating point — one blocking round trip per chunk
    # makes small chunks prohibitive for it. On TPU the continuous
    # scheduler uses the same prefill_chunk but a small per-row
    # mixed-step budget (max_tokens_per_step): the pipeline makes small
    # steps cheap, so decode rows stop paying for prompt-wide batch
    # rows under churn. On CPU steps are width-flat (see docstring), so
    # the continuous side runs full-width mixed steps (budget 0).
    prefill_chunk = 32 if on_tpu else 24
    mixed_budget = 8 if on_tpu else 0
    if not on_tpu and kernels == "pallas":
        _log("serve_continuous: forcing kernels=xla off-TPU (interpret-"
             "mode pallas would dominate the measurement)")
        kernels = "xla"

    prompts = [
        [(i * 37 + j * 11 + 3) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_req)
    ]

    def make_rm(continuous):
        sc = ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=prefill_chunk,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            # ample pool: churn, not preemption, is the variable here
            max_cached_tokens=n_slots * (prompt_len + n_new + page_size),
            continuous_batching=continuous,
            max_tokens_per_step=mixed_budget if continuous else 0,
            # retrace sentinel (analysis/retrace.py): any steady-state
            # step recompile raises at the offending dispatch (and the
            # measured run's compile counters are asserted zero below)
            # — a host-side shape/dtype drift would otherwise hide as
            # scheduler noise in this phase's throughput numbers
            sanitizers=("retrace",),
        )
        rm = RequestManager(InferenceEngine(llama, cfg, params, sc))
        rm.generate(prompts[:n_slots], max_new_tokens=4)  # warm/compile
        return rm

    def percentiles(vals):
        if not vals:
            return 0.0, 0.0
        import numpy as np

        return (float(np.percentile(vals, 50)), float(np.percentile(vals, 99)))

    def run(rm, arrival_s):
        """Open-loop run: requests arrive on the wall-clock Poisson
        schedule; the scheduler is stepped until everything drains."""
        rids, outs = [], {}
        due = list(zip(arrival_s, prompts))
        t0 = time.perf_counter()
        while due or any(
            rm.requests[r].status.value not in ("completed", "error")
            for r in rids
        ):
            now = time.perf_counter() - t0
            while due and due[0][0] <= now:
                _, p = due.pop(0)
                rids.append(rm.submit(p, max_new_tokens=n_new))
            if not rm.step() and due:
                time.sleep(max(0.0, due[0][0] - (time.perf_counter() - t0)))
        rm.drain()
        wall = time.perf_counter() - t0
        tokens = 0
        ttft, tpot = [], []
        for r in rids:
            req = rm.requests[r]
            out = req.output_tokens
            outs[r] = list(out)
            tokens += len(out)
            ttft.append(req.profile.ttft_s * 1e3)
            tpot.append(req.profile.tpot_s(len(out)) * 1e3)
        return {
            "tps": tokens / wall,
            "ttft": percentiles(ttft),
            "tpot": percentiles(tpot),
            "outputs": [outs[r] for r in rids],
            "stats": rm.stats.snapshot(),
        }

    # Calibrate the Poisson arrival rate to the continuous scheduler's
    # closed-loop capacity: arrivals then span the WHOLE run (sustained
    # churn — every iteration has prompts in flight) instead of a
    # front-loaded burst followed by a pure-decode drain both schedulers
    # serve identically. The slower scheduler falls behind the same
    # offered load, which is exactly the claim under test.
    rm_cont = make_rm(continuous=True)
    t0 = time.perf_counter()
    rm_cont.generate(prompts[:n_slots], max_new_tokens=n_new)
    est_tps = (n_slots * n_new) / (time.perf_counter() - t0)
    offered = 1.0 * est_tps
    import numpy as np

    rng = np.random.default_rng(42)
    arrival_s = np.cumsum(
        rng.exponential(scale=n_new / offered, size=n_req)
    ).tolist()

    # fresh stats for the measured run (the calibration generate above
    # already warmed every program shape)
    rm_cont.stats = type(rm_cont.stats)()
    cont = run(rm_cont, arrival_s)
    del rm_cont
    base = run(make_rm(continuous=False), arrival_s)

    assert cont["outputs"] == base["outputs"], (
        "continuous vs flush-on-admit scheduler outputs diverged"
    )
    # stats were reset after warmup, so compiles/retraces here count the
    # MEASURED run only: steady state must replay warmed programs
    assert cont["stats"]["retraces"] == 0 and base["stats"]["retraces"] == 0, (
        f"steady-state recompiles in the measured serve run: "
        f"cont={cont['stats']['retraces']} base={base['stats']['retraces']}"
    )
    ratio = cont["tps"] / max(1e-9, base["tps"])
    emit(
        "continuous_serve_tokens_per_sec_per_chip",
        round(cont["tps"], 2),
        "tokens/sec/chip",
        vs_baseline=ratio,
        kernels=kernels,
        n_requests=n_req,
        n_slots=n_slots,
        new_tokens_per_request=n_new,
        prompt_len=prompt_len,
        prefill_chunk=prefill_chunk,
        max_tokens_per_step=mixed_budget,
        offered_tokens_per_sec=round(offered, 1),
        ttft_p50_ms=round(cont["ttft"][0], 1),
        ttft_p99_ms=round(cont["ttft"][1], 1),
        tpot_p50_ms=round(cont["tpot"][0], 2),
        tpot_p99_ms=round(cont["tpot"][1], 2),
        baseline_tokens_per_sec=round(base["tps"], 2),
        baseline_ttft_p50_ms=round(base["ttft"][0], 1),
        baseline_ttft_p99_ms=round(base["ttft"][1], 1),
        baseline_tpot_p50_ms=round(base["tpot"][0], 2),
        baseline_tpot_p99_ms=round(base["tpot"][1], 2),
        scheduler_parity=1,
        mean_occupancy=cont["stats"]["mean_occupancy"],
        mean_budget_fill=cont["stats"]["mean_budget_fill"],
        pipeline_drains=cont["stats"]["pipeline_drains"],
        jit_compiles_measured=cont["stats"]["compiles"],
        steady_state_recompiles=cont["stats"]["retraces"],
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return cont["tps"]


def serve_prefix_bench(on_tpu, kernels):
    """Automatic prefix caching under a shared-system-prompt workload:
    Poisson arrivals where every prompt = one LONG shared system prefix
    + a short unique user tail (the serving pattern the cache exists
    for: templates, few-shot headers, multi-turn resends). Same paged
    continuous-batching scheduler with ``prefix_caching`` on vs off;
    cached admissions splice the system prompt's pages and prefill only
    the tail. Reports tokens/sec/chip, TTFT p50/p99 both modes, and the
    measured hit rate; greedy outputs are asserted identical (the hit
    path must be bitwise — tests/test_prefix_cache.py).

    Measurement caveat (CPU): as with serve_continuous, XLA:CPU runs
    steps inline and nearly width-flat, so skipping prefill compute
    barely moves wall-clock there — the CPU run is a parity/accounting
    smoke and chiefly shows the TTFT win (fewer chunks before the first
    sampled token). The throughput claim is an accelerator property:
    on TPU every skipped prefill chunk is a real R×C step saved."""
    import jax

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import InferenceEngine, RequestManager, ServingConfig

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 32
    n_req = 96 if on_tpu else 64
    n_new = 24 if on_tpu else 8
    sys_len = 96 if on_tpu else 32     # the shared prefix (page-aligned)
    tail_len = 16 if on_tpu else 6     # unique per request
    page_size = 32 if on_tpu else 8
    prefill_chunk = 32 if on_tpu else 8
    if not on_tpu and kernels == "pallas":
        _log("serve_prefix: forcing kernels=xla off-TPU (interpret-mode "
             "pallas would dominate the measurement)")
        kernels = "xla"

    prompt_len = sys_len + tail_len
    system = [(j * 11 + 3) % cfg.vocab_size for j in range(sys_len)]
    prompts = [
        system + [(i * 37 + j * 13 + 5) % cfg.vocab_size
                  for j in range(tail_len)]
        for i in range(n_req)
    ]

    def make_rm(caching):
        sc = ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=prefill_chunk,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            # room for live requests + a cached system prompt, but
            # pressure enough that LRU eviction stays exercised
            max_cached_tokens=n_slots * (prompt_len + n_new + page_size),
            prefix_caching=caching,
            # retrace sentinel: splice/COW churn must replay the warmed
            # programs — a recompile raises instead of skewing the A/B
            sanitizers=("retrace",),
        )
        rm = RequestManager(InferenceEngine(llama, cfg, params, sc))
        rm.generate(prompts[:n_slots], max_new_tokens=4)  # warm/compile
        rm.stats = type(rm.stats)()
        return rm

    def percentiles(vals):
        import numpy as np

        if not vals:
            return 0.0, 0.0
        return (float(np.percentile(vals, 50)), float(np.percentile(vals, 99)))

    def run(rm, arrival_s):
        rids = []
        due = list(zip(arrival_s, prompts))
        t0 = time.perf_counter()
        while due or any(
            rm.requests[r].status.value not in ("completed", "error")
            for r in rids
        ):
            now = time.perf_counter() - t0
            while due and due[0][0] <= now:
                _, p = due.pop(0)
                rids.append(rm.submit(p, max_new_tokens=n_new))
            if not rm.step() and due:
                time.sleep(max(0.0, due[0][0] - (time.perf_counter() - t0)))
        rm.drain()
        wall = time.perf_counter() - t0
        tokens, ttft = 0, []
        outs = []
        for r in rids:
            req = rm.requests[r]
            outs.append(list(req.output_tokens))
            tokens += len(req.output_tokens)
            ttft.append(req.profile.ttft_s * 1e3)
        return {
            "tps": tokens / wall,
            "ttft": percentiles(ttft),
            "outputs": outs,
            "stats": rm.stats.snapshot(),
        }

    # calibrate offered load to the CACHING-OFF capacity so both modes
    # face identical sustained churn; the warm/cached side then clears
    # the same offered stream with less prefill work per admission
    rm_off = make_rm(caching=False)
    t0 = time.perf_counter()
    rm_off.generate(prompts[:n_slots], max_new_tokens=n_new)
    est_tps = (n_slots * n_new) / (time.perf_counter() - t0)
    import numpy as np

    rng = np.random.default_rng(42)
    arrival_s = np.cumsum(
        rng.exponential(scale=n_new / est_tps, size=n_req)
    ).tolist()

    rm_off.stats = type(rm_off.stats)()
    base = run(rm_off, arrival_s)
    del rm_off
    warm = run(make_rm(caching=True), arrival_s)

    assert warm["outputs"] == base["outputs"], (
        "prefix-cached vs cold scheduler outputs diverged"
    )
    s = warm["stats"]
    # zero steady-state recompiles on both sides of the A/B (the
    # copy_page COW program may legitimately compile ONCE mid-run —
    # only RE-compiles of a known step key are the hazard)
    assert s["retraces"] == 0 and base["stats"]["retraces"] == 0, (
        f"steady-state recompiles: warm={s['retraces']} "
        f"base={base['stats']['retraces']}"
    )
    total_prompt = n_req * prompt_len
    emit(
        "prefix_serve_tokens_per_sec_per_chip",
        round(warm["tps"], 2),
        "tokens/sec/chip",
        vs_baseline=warm["tps"] / max(1e-9, base["tps"]),
        kernels=kernels,
        n_requests=n_req,
        n_slots=n_slots,
        new_tokens_per_request=n_new,
        system_prompt_len=sys_len,
        prompt_len=prompt_len,
        page_size=page_size,
        prefix_hit_rate=s["prefix_hit_rate"],
        prefix_hit_tokens=s["prefix_hit_tokens"],
        prefill_tokens_saved_frac=round(
            s["prefix_hit_tokens"] / max(1, total_prompt), 4
        ),
        prefix_evictions=s["prefix_evictions"],
        prefix_cows=s["prefix_cows"],
        jit_compiles_measured=s["compiles"],
        steady_state_recompiles=s["retraces"],
        ttft_p50_ms=round(warm["ttft"][0], 1),
        ttft_p99_ms=round(warm["ttft"][1], 1),
        baseline_ttft_p50_ms=round(base["ttft"][0], 1),
        baseline_ttft_p99_ms=round(base["ttft"][1], 1),
        baseline_tokens_per_sec=round(base["tps"], 2),
        output_parity=1,
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return warm["tps"]


def serve_paged_q_bench(on_tpu, kernels):
    """Quantized paged KV cache (serve/kv_quant.py: int8 pages +
    per-page-per-KV-head amax scales, dequant fused into the ragged
    paged attention read — serve/kernels.py) vs the bf16 paged pool at
    the SAME ``max_cached_tokens`` HBM budget: 64 request slots under
    Poisson arrivals. The budget is priced in bf16 lines and set to
    ~56% of the 64-slot worst case, so the bf16 pool saturates and
    recompute-preempts under load the int8 pool — which the same
    budget buys ~2x the physical pages for (asserted ≥ 1.9x) —
    absorbs. Reports tokens/sec/chip, TTFT/TPOT p50/p99 for both
    pools, measured KV-HBM-bytes-per-live-token at peak occupancy, and
    the max concurrent slots each pool sustained (with its preemption
    count).

    Output parity: int8 KV is lossy — a near-tied greedy argmax can
    flip, and one flip cascades through the rest of that request — so
    exact token equality is not the contract. The run asserts
    per-position agreement ≥ 0.75 across all requests (measured logit
    error is ~0.3% of the logit range; the documented engine-level
    tolerance is 2% of max|logit| — tests/test_kv_quant.py, README
    "Quantized KV cache"). Bitwise run-to-run determinism of the int8
    pool itself is a tier-1 test, not re-measured here.

    Measurement caveat (CPU): XLA:CPU decode is compute-bound, not
    KV-bandwidth-bound, so halving KV read bytes barely moves
    tokens/sec there (the dequant even adds FLOPs) — off-TPU the
    throughput ratio is a parity/scheduling smoke and the phase's real
    signal is capacity: pages, bytes/live-token, preemptions. On TPU
    the halved KV stream is the decode hot loop's bandwidth."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import InferenceEngine, RequestManager, ServingConfig

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 64
    n_req = 128 if on_tpu else 96
    n_new = 32 if on_tpu else 16
    prompt_len = 64 if on_tpu else 24
    page_size = 64 if on_tpu else 16
    prefill_chunk = 32 if on_tpu else 24
    # "int8-KV vs bf16-KV": the fp side stores bf16 pages on BOTH
    # platforms (CPU model weights stay f32 — only the cache dtype is
    # pinned) so the pages-per-budget ratio under test is the 2x one,
    # not the trivial 4x a f32 baseline would show.
    cache_dtype = jnp.bfloat16
    # ~56% of the 64-slot worst case: 36 full-length slots of bf16
    # pages, ~71 of int8 — the A/B's whole point is that only one side
    # fits the offered concurrency.
    budget = (n_slots // 2 + 4) * (prompt_len + n_new + page_size)
    if not on_tpu and kernels == "pallas":
        _log("serve_paged_q: forcing kernels=xla off-TPU (interpret-mode "
             "pallas would dominate the measurement)")
        kernels = "xla"

    prompts = [
        [(i * 37 + j * 11 + 3) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_req)
    ]

    def make_rm(kv_quant):
        sc = ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=prefill_chunk,
            max_spec_tree_tokens=16,
            cache_dtype=cache_dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            max_cached_tokens=budget,
            kv_quant=kv_quant,
            # retrace sentinel: quantized pools add scale operands to
            # every step — a shape/dtype drift there would recompile
            # mid-run and hide as throughput noise; it raises instead
            sanitizers=("retrace",),
        )
        rm = RequestManager(InferenceEngine(llama, cfg, params, sc))
        rm.generate(prompts[:n_slots], max_new_tokens=4)  # warm/compile
        rm.stats = type(rm.stats)()
        return rm

    def percentiles(vals):
        import numpy as np

        if not vals:
            return 0.0, 0.0
        return (float(np.percentile(vals, 50)), float(np.percentile(vals, 99)))

    def run(rm, arrival_s):
        """Open-loop Poisson run (serve_continuous's driver) that also
        tracks peak concurrency and snapshots allocated-KV-bytes per
        live token at the occupancy peak."""
        eng = rm.engine
        rids = []
        due = list(zip(arrival_s, prompts))
        max_live = 0
        peak_tokens, peak_bytes = 0, 0
        t0 = time.perf_counter()
        while due or any(
            rm.requests[r].status.value not in ("completed", "error")
            for r in rids
        ):
            now = time.perf_counter() - t0
            while due and due[0][0] <= now:
                _, p = due.pop(0)
                rids.append(rm.submit(p, max_new_tokens=n_new))
            stepped = rm.step()
            live = [rm.requests[r] for r in rids if rm.requests[r].slot >= 0]
            max_live = max(max_live, len(live))
            live_tokens = sum(r.n_cached for r in live)
            if live_tokens >= peak_tokens:
                peak_tokens = live_tokens
                peak_bytes = eng.kv_allocated_bytes()
            if not stepped and due:
                time.sleep(max(0.0, due[0][0] - (time.perf_counter() - t0)))
        rm.drain()
        wall = time.perf_counter() - t0
        tokens = 0
        ttft, tpot, outs = [], [], []
        for r in rids:
            req = rm.requests[r]
            out = req.output_tokens
            outs.append(list(out))
            tokens += len(out)
            ttft.append(req.profile.ttft_s * 1e3)
            tpot.append(req.profile.tpot_s(len(out)) * 1e3)
        return {
            "tps": tokens / wall,
            "ttft": percentiles(ttft),
            "tpot": percentiles(tpot),
            "outputs": outs,
            "max_live": max_live,
            "bytes_per_live_token": peak_bytes / max(1, peak_tokens),
            "stats": rm.stats.snapshot(),
        }

    # --- int8 pool (also calibrates the offered load: arrivals span
    # the whole run at the quantized engine's closed-loop capacity, so
    # the bf16 side faces sustained churn it cannot fully seat) ---
    rm_q = make_rm("int8")
    pages_q = rm_q.engine.pager.num_pages
    t0 = time.perf_counter()
    rm_q.generate(prompts[:n_slots], max_new_tokens=n_new)
    est_tps = (n_slots * n_new) / (time.perf_counter() - t0)
    import numpy as np

    rng = np.random.default_rng(42)
    arrival_s = np.cumsum(
        rng.exponential(scale=n_new / est_tps, size=n_req)
    ).tolist()
    rm_q.stats = type(rm_q.stats)()  # calibration warmed all shapes
    q = run(rm_q, arrival_s)
    del rm_q

    # --- bf16 pool, same budget, same arrival schedule ---
    rm_fp = make_rm(None)
    pages_fp = rm_fp.engine.pager.num_pages
    fp = run(rm_fp, arrival_s)
    del rm_fp

    # same budget must expose ~2x the pages (the acceptance bar; the
    # shortfall from exactly 2x is the per-page f32 scale rows)
    pages_ratio = pages_q / max(1, pages_fp)
    assert pages_ratio >= 1.9, (
        f"int8 pool exposes only {pages_ratio:.3f}x the bf16 pages "
        f"({pages_q} vs {pages_fp}) at max_cached_tokens={budget}"
    )
    # greedy parity within the documented tolerance (see docstring)
    flat_fp = [t for o in fp["outputs"] for t in o]
    flat_q = [t for o in q["outputs"] for t in o]
    agree = (
        sum(a == b for a, b in zip(flat_q, flat_fp))
        / max(1, min(len(flat_q), len(flat_fp)))
    )
    assert len(flat_q) == len(flat_fp) and agree >= 0.75, (
        f"int8-KV greedy outputs diverged beyond tolerance: "
        f"agreement={agree:.4f} ({len(flat_q)} vs {len(flat_fp)} tokens)"
    )
    assert q["stats"]["retraces"] == 0 and fp["stats"]["retraces"] == 0, (
        f"steady-state recompiles in the measured serve run: "
        f"int8={q['stats']['retraces']} bf16={fp['stats']['retraces']}"
    )
    if fp["stats"]["preemptions"] == 0:
        _log("serve_paged_q: bf16 pool never preempted — offered load "
             "did not saturate the fp pool; capacity delta is still "
             "reported via pages/max_live")

    emit(
        "paged_q_kv_hbm_bytes_per_live_token",
        round(q["bytes_per_live_token"], 1),
        "bytes/token",
        # <1: the quantized pool's peak-occupancy HBM cost per live
        # token vs the bf16 pool's, same budget, same workload
        vs_baseline=(
            q["bytes_per_live_token"] / max(1e-9, fp["bytes_per_live_token"])
        ),
        kv_quant="int8",
        fp_bytes_per_live_token=round(fp["bytes_per_live_token"], 1),
        pool_pages_int8=pages_q,
        pool_pages_bf16=pages_fp,
        pool_pages_ratio=round(pages_ratio, 3),
        page_size=page_size,
        max_cached_tokens=budget,
        platform=_platform(),
    )
    emit(
        "paged_q_serve_tokens_per_sec_per_chip",
        round(q["tps"], 2),
        "tokens/sec/chip",
        vs_baseline=q["tps"] / max(1e-9, fp["tps"]),
        kernels=kernels,
        kv_quant="int8",
        n_requests=n_req,
        n_slots=n_slots,
        new_tokens_per_request=n_new,
        prompt_len=prompt_len,
        max_cached_tokens=budget,
        pool_pages_ratio=round(pages_ratio, 3),
        kv_hbm_bytes_per_live_token=round(q["bytes_per_live_token"], 1),
        fp_kv_hbm_bytes_per_live_token=round(fp["bytes_per_live_token"], 1),
        max_concurrent_slots_int8=q["max_live"],
        max_concurrent_slots_bf16=fp["max_live"],
        preemptions_int8=q["stats"]["preemptions"],
        preemptions_bf16=fp["stats"]["preemptions"],
        ttft_p50_ms=round(q["ttft"][0], 1),
        ttft_p99_ms=round(q["ttft"][1], 1),
        tpot_p50_ms=round(q["tpot"][0], 2),
        tpot_p99_ms=round(q["tpot"][1], 2),
        baseline_tokens_per_sec=round(fp["tps"], 2),
        baseline_ttft_p50_ms=round(fp["ttft"][0], 1),
        baseline_ttft_p99_ms=round(fp["ttft"][1], 1),
        baseline_tpot_p50_ms=round(fp["tpot"][0], 2),
        baseline_tpot_p99_ms=round(fp["tpot"][1], 2),
        token_agreement=round(agree, 4),
        jit_compiles_measured=q["stats"]["compiles"],
        steady_state_recompiles=q["stats"]["retraces"],
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return q["tps"]


def serve_kv_hierarchy_bench(on_tpu, kernels):
    """Hierarchical KV cache (PR 7): int4 packed-nibble pages + the
    host-RAM spill tier for cold prefix pages, measured together
    because they raise the same ceiling — how much cached KV a chip's
    HBM budget effectively serves.

    Part 1 — capacity ladder: bf16 vs int8 vs int4 page pools at the
    SAME ``max_cached_tokens`` HBM budget. int4 stores two codes per
    byte along dk, so the asserted bars are pages_int8/bf16 ≥ 1.9x and
    pages_int4/bf16 ≥ 3.8x (the shortfall from 2x/4x is the per-page
    f32 scale rows). Also reports the int4 pool's measured
    bytes-per-live-token at peak occupancy (feeds the bench summary's
    ``kv_bytes_per_live_token``).

    Part 2 — spill-vs-eviction A/B on a 64-slot shared-prefix Poisson
    workload (int4 pages, prefix caching on, pool sized so family
    prefixes get reclaimed under churn): with ``host_cache_bytes`` the
    reclaim path spills to host and later matches re-admit (host hit);
    without it the pages are evicted and re-prefilled. Shared prefixes
    are page-ALIGNED with unique per-request tails and cache_policy
    "prefill", so both sides are bitwise-comparable even over the
    lossy int4 pool — output parity is asserted exactly, alongside
    spills/readmits > 0, host_hit_rate, TTFT p50/p99 both modes and
    zero steady-state recompiles under the retrace guard.

    Measurement caveat (CPU): XLA:CPU runs steps inline and nearly
    width-flat, so the skipped re-prefill work barely moves wall-clock
    tokens/sec there — off-TPU the phase's real signal is capacity
    (the pages ladder), the counters, and TTFT (fewer chunks before
    the first sampled token). On TPU every re-admitted page is a
    prefill chunk of HBM-bound compute saved for one async PCIe copy.
    """
    import jax

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import InferenceEngine, RequestManager, ServingConfig

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 64
    n_fam = 8 if on_tpu else 6          # distinct shared system prompts
    reqs_per_fam = 8 if on_tpu else 6
    rounds = 2                          # each family re-served after churn
    n_new = 24 if on_tpu else 8
    sys_len = 128 if on_tpu else 32     # page-aligned shared prefix
    page_size = 64 if on_tpu else 16
    # the unique tail fills exactly ONE page: every published block is
    # then FULL, so every cache match — including a preempted request
    # re-matching its own published prompt — ends page-ALIGNED. That
    # is what makes the lossy int4 A/B bitwise-comparable: a partial
    # block would COW and append at a scale whose history differs
    # between the spill and eviction runs (README "Hierarchical KV
    # cache" documents the asymmetry; policy "prefill" keeps generated
    # tails out of the tree for the same reason).
    tail_len = page_size
    prefill_chunk = 64 if on_tpu else 16
    if not on_tpu and kernels == "pallas":
        _log("serve_kv_hierarchy: forcing kernels=xla off-TPU "
             "(interpret-mode pallas would dominate the measurement)")
        kernels = "xla"
    assert sys_len % page_size == 0  # aligned matches keep int4 bitwise

    import jax.numpy as jnp

    cache_dtype = jnp.bfloat16
    prompt_len = sys_len + tail_len

    def fam_prompt(f, g):
        sys_p = [(j * 11 + f * 41 + 3) % cfg.vocab_size
                 for j in range(sys_len)]
        # the tail's FIRST token is globally unique (g < vocab): a
        # repeated first token would let a later request partial-match
        # another request's cached tail block MID-page, and the COW +
        # append over a quantized page re-introduces the scale-history
        # asymmetry the aligned design exists to exclude (README
        # "Hierarchical KV cache"; tests/test_kv_hierarchy.py)
        tail = [(g + 5 + j * 7) % cfg.vocab_size for j in range(tail_len)]
        return sys_p + tail

    # round-robin rounds over families: family f's prefix goes cold
    # while the other families churn, then gets re-requested
    fams = [
        f
        for _ in range(rounds)
        for f in range(n_fam)
        for _ in range(reqs_per_fam)
    ]
    assert len(fams) + 5 < cfg.vocab_size  # unique tail starts
    prompts = [fam_prompt(f, g) for g, f in enumerate(fams)]
    n_req = len(prompts)

    # ---- part 1: pages-per-budget ladder -----------------------------
    # the shared budget all three rungs convert: about half the
    # 64-slot live worst case in bf16 pages
    budget = (n_slots // 2) * (prompt_len + n_new + page_size)

    def make_rm(kv_quant, host_bytes, warm=True, max_tokens=None):
        sc = ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=prefill_chunk,
            max_spec_tree_tokens=16,
            cache_dtype=cache_dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            max_cached_tokens=max_tokens or budget,
            kv_quant=kv_quant,
            prefix_caching=True,
            # prompts only: generated tails would partial-match later
            # requests of the same family and re-introduce the COW
            # append asymmetry the aligned design excludes
            cache_policy="prefill",
            host_cache_bytes=host_bytes,
            # a recompile mid-run would hide as throughput noise —
            # the sentinel raises instead
            sanitizers=("retrace",),
        )
        rm = RequestManager(InferenceEngine(llama, cfg, params, sc))
        if warm:
            rm.generate(prompts[:n_slots], max_new_tokens=4)
            rm.stats = type(rm.stats)()
        return rm

    pages = {
        name: make_rm(name, None, warm=False).engine.pager.num_pages
        for name in (None, "int8", "int4")
    }
    r8 = pages["int8"] / max(1, pages[None])
    r4 = pages["int4"] / max(1, pages[None])
    assert r8 >= 1.9, (
        f"int8 pool exposes only {r8:.3f}x the bf16 pages "
        f"({pages['int8']} vs {pages[None]})"
    )
    assert r4 >= 3.8, (
        f"int4 pool exposes only {r4:.3f}x the bf16 pages "
        f"({pages['int4']} vs {pages[None]}) — the packed-nibble "
        "acceptance bar is 3.8x"
    )

    def percentiles(vals):
        import numpy as np

        if not vals:
            return 0.0, 0.0
        return (float(np.percentile(vals, 50)), float(np.percentile(vals, 99)))

    def run(rm, arrival_s):
        eng = rm.engine
        rids = []
        due = list(zip(arrival_s, prompts))
        peak_tokens, peak_bytes = 0, 0
        t0 = time.perf_counter()
        while due or any(
            rm.requests[r].status.value not in ("completed", "error")
            for r in rids
        ):
            now = time.perf_counter() - t0
            while due and due[0][0] <= now:
                _, p = due.pop(0)
                rids.append(rm.submit(p, max_new_tokens=n_new))
            stepped = rm.step()
            live = [rm.requests[r] for r in rids if rm.requests[r].slot >= 0]
            live_tokens = sum(r.n_cached for r in live)
            if live_tokens >= peak_tokens:
                peak_tokens = live_tokens
                peak_bytes = eng.kv_allocated_bytes()
            if not stepped and due:
                time.sleep(max(0.0, due[0][0] - (time.perf_counter() - t0)))
        rm.drain()
        wall = time.perf_counter() - t0
        tokens, ttft, outs = 0, [], []
        for r in rids:
            req = rm.requests[r]
            outs.append(list(req.output_tokens))
            tokens += len(req.output_tokens)
            ttft.append(req.profile.ttft_s * 1e3)
        return {
            "tps": tokens / wall,
            "ttft": percentiles(ttft),
            "outputs": outs,
            "bytes_per_live_token": peak_bytes / max(1, peak_tokens),
            "stats": rm.stats.snapshot(),
        }

    # ---- part 2: spill vs plain eviction (int4 pages) ----------------
    # The A/B needs real pressure ON THE INT4 POOL: the ladder budget
    # converts to ~4x the pages and would absorb the whole prefix
    # working set. Size the pool BELOW the workload's cached working
    # set — one round's per-request tail blocks (cache_policy
    # "prefill" publishes those too) plus every family's system pages
    # — with a quarter of the slots' worth of live headroom: round 2
    # then cannot proceed without reclaiming round 1's cold pages, so
    # idle family prefixes spill (or evict, on the baseline side) and
    # get re-admitted when their family comes back around.
    target_pages = (
        n_fam * reqs_per_fam      # one round of unique tail blocks
        + 2 * (sys_len // page_size) * n_fam  # every family's sys pages
        + n_slots // 4            # live-set headroom
    )
    budget_ab = max(
        prompt_len + n_new + page_size,
        int(budget * target_pages / max(1, pages["int4"])),
    )

    # calibrate offered load on the eviction side so both modes face
    # the same sustained churn
    rm_evict = make_rm("int4", None, max_tokens=budget_ab)
    t0 = time.perf_counter()
    rm_evict.generate(prompts[:n_slots], max_new_tokens=n_new)
    est_tps = (n_slots * n_new) / (time.perf_counter() - t0)
    import numpy as np

    rng = np.random.default_rng(42)
    arrival_s = np.cumsum(
        rng.exponential(scale=n_new / est_tps, size=n_req)
    ).tolist()
    rm_evict.stats = type(rm_evict.stats)()
    base = run(rm_evict, arrival_s)
    del rm_evict

    # 1 GiB host tier: the host LRU rarely binds — the A/B isolates
    # spill-vs-evict, not host-budget pressure
    rm_spill = make_rm("int4", 1 << 30, max_tokens=budget_ab)
    spill = run(rm_spill, arrival_s)
    host_pages_left = rm_spill.prefix_cache.host_pages
    del rm_spill

    assert spill["outputs"] == base["outputs"], (
        "host-spill vs plain-eviction outputs diverged (the aligned "
        "shared-prefix design should make them bitwise)"
    )
    s, b = spill["stats"], base["stats"]
    assert s["retraces"] == 0 and b["retraces"] == 0, (
        f"steady-state recompiles: spill={s['retraces']} "
        f"evict={b['retraces']}"
    )
    if not (s["spills"] and s["readmits"]):
        _log("serve_kv_hierarchy: WARNING — churn produced "
             f"spills={s['spills']} readmits={s['readmits']}; the pool "
             "budget did not pressure the prefix working set")

    emit(
        "kv_hier_pool_pages_ratio_int4",
        round(r4, 3),
        "ratio",
        vs_baseline=r4 / 4.0,  # vs the ideal 4x
        pool_pages_bf16=pages[None],
        pool_pages_int8=pages["int8"],
        pool_pages_int4=pages["int4"],
        pool_pages_ratio_int8=round(r8, 3),
        page_size=page_size,
        max_cached_tokens=budget,
        platform=_platform(),
    )
    emit(
        "kv_hier_kv_hbm_bytes_per_live_token",
        round(spill["bytes_per_live_token"], 1),
        "bytes/token",
        kv_quant="int4",
        page_size=page_size,
        platform=_platform(),
    )
    emit(
        "kv_hier_serve_tokens_per_sec_per_chip",
        round(spill["tps"], 2),
        "tokens/sec/chip",
        vs_baseline=spill["tps"] / max(1e-9, base["tps"]),
        kernels=kernels,
        kv_quant="int4",
        n_requests=n_req,
        n_slots=n_slots,
        n_families=n_fam,
        rounds=rounds,
        new_tokens_per_request=n_new,
        system_prompt_len=sys_len,
        prompt_len=prompt_len,
        max_cached_tokens=budget_ab,
        ladder_budget=budget,
        spills=s["spills"],
        readmits=s["readmits"],
        host_hit_tokens=s["host_hit_tokens"],
        host_hit_rate=s["host_hit_rate"],
        host_bytes_peak=s["host_bytes"],
        host_pages_left=host_pages_left,
        prefix_hit_rate=s["prefix_hit_rate"],
        evictions_spill_mode=s["prefix_evictions"],
        evictions_baseline=b["prefix_evictions"],
        ttft_p50_ms=round(spill["ttft"][0], 1),
        ttft_p99_ms=round(spill["ttft"][1], 1),
        baseline_ttft_p50_ms=round(base["ttft"][0], 1),
        baseline_ttft_p99_ms=round(base["ttft"][1], 1),
        baseline_tokens_per_sec=round(base["tps"], 2),
        output_parity=1,
        jit_compiles_measured=s["compiles"],
        steady_state_recompiles=s["retraces"],
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return spill["tps"]


def serve_long_context_bench(on_tpu, kernels):
    """Context-parallel long-context serving (ServingConfig.kv_shard=
    "context", PR 11): one request's KV pages stripe across sequence
    shards, ``max_cached_tokens`` prices ONE shard, and prompts beyond
    a single shard's pool serve at the aggregate capacity.

    Prompt-length ladder (8k / 32k / synthetic-100k on TPU; the CPU
    smoke runs the same three-rung SHAPE at scale-model lengths —
    detail records the actual token counts), CP-on vs CP-off at the
    SAME per-shard budget:

      * the two lower rungs fit one shard's budget: both modes serve
        them and their greedy outputs are asserted BITWISE identical
        (on a seq-degree-1 mesh CP attention is the table-gather XLA
        fallback — bit-for-bit the CP-off math, serve/kernels.py);
      * the TOP rung strictly exceeds one shard's budget: CP-off is
        asserted to fail with a terminal GenerationResult.error (the
        PR-2 unservable contract) while CP-on serves it — the
        capability this mode exists for;
      * both arms run under the strict retrace sentinel and assert
        zero steady-state recompiles (the churn variant lives in
        tests/test_long_context.py::TestCpRetrace).

    Reports tokens/sec over the ladder plus per-rung TTFT p50 — the
    top rung's TTFT feeds the summary's ``long_context_ttft_s``.

    Measurement caveat (CPU): XLA:CPU is compute-bound and single-
    device, so CP-on vs CP-off throughput here is a parity/capability
    smoke, NOT the bandwidth claim — on a real seq-sharded TPU mesh
    each shard reads only its resident pages (ring ragged paged
    attention) and the aggregate-HBM-bandwidth win is what the chip
    measures.
    """
    import jax

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import (
        InferenceEngine, RequestManager, ServingConfig,
    )

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    if not on_tpu and kernels == "pallas":
        _log("serve_long_context: forcing kernels=xla off-TPU")
        kernels = "xla"

    cp = 4
    n_new = 24 if on_tpu else 16
    if on_tpu:
        ladder = [("8k", 8192), ("32k", 32768), ("synthetic-100k", 102400)]
        page_size = 128
        prefill_chunk = 256
    else:
        # scale-model rungs: same three-rung ladder shape, sized so the
        # top rung still strictly exceeds one shard's budget
        ladder = [("8k", 256), ("32k", 512), ("synthetic-100k", 1536)]
        page_size = 32
        prefill_chunk = 128
    top_len = ladder[-1][1]
    # per-shard budget: covers the MID rung with decode headroom,
    # strictly below the TOP rung — the aggregate (x cp) covers it
    budget = ladder[1][1] + n_new + 4 * page_size
    assert budget < top_len and cp * budget > top_len + n_new

    import jax.numpy as jnp

    def make_rm(**kw):
        sc = ServingConfig(
            max_requests_per_batch=2,
            max_sequence_length=top_len + n_new + 8,
            prefill_chunk=prefill_chunk,
            max_spec_tree_tokens=16,
            cache_dtype=jnp.bfloat16 if on_tpu else jnp.float32,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            max_cached_tokens=budget,
            sanitizers=("retrace",),
            **kw,
        )
        return RequestManager(InferenceEngine(llama, cfg, params, sc))

    def rung_prompt(n, seed):
        return [(seed + 11 * j) % cfg.vocab_size for j in range(n)]

    def run_ladder(rm, servable_only):
        outs, ttft = {}, {}
        tokens = 0
        t0 = time.perf_counter()
        for i, (name, n) in enumerate(ladder):
            if servable_only and n + 1 > budget:
                continue
            r = rm.generate([rung_prompt(n, 7 + i)],
                            max_new_tokens=n_new)[0]
            assert r.error is None, f"{name}: {r.error}"
            outs[name] = list(r.output_tokens)
            ttft[name] = r.profile.ttft_s
            tokens += len(r.output_tokens)
        wall = time.perf_counter() - t0
        return outs, ttft, tokens / max(1e-9, wall), rm.stats.snapshot()

    # CP-off arm: same per-shard budget, single pool
    rm_off = make_rm()
    off_outs, off_ttft, off_tps, off_stats = run_ladder(
        rm_off, servable_only=True
    )
    # the top rung is UNSERVABLE without CP: terminal error, not a hang
    r = rm_off.generate([rung_prompt(top_len, 9)], max_new_tokens=4)[0]
    assert r.error is not None and "budget" in r.error, (
        f"top rung should be unservable CP-off (got error={r.error!r})"
    )
    del rm_off

    # CP-on arm: the same budget PER SHARD, striped over cp shards
    rm_cp = make_rm(kv_shard="context", context_shards=cp)
    cp_outs, cp_ttft, cp_tps, cp_stats = run_ladder(
        rm_cp, servable_only=False
    )
    rm_cp.drain()
    rm_cp.engine.pager.check_no_leaks()
    del rm_cp

    for name in off_outs:
        assert cp_outs[name] == off_outs[name], (
            f"CP-on vs CP-off outputs diverged on the {name} rung"
        )
    assert ladder[-1][0] in cp_outs, "CP-on failed to serve the top rung"
    assert cp_stats["retraces"] == 0 and off_stats["retraces"] == 0, (
        f"steady-state recompiles: cp={cp_stats['retraces']} "
        f"off={off_stats['retraces']}"
    )

    emit(
        "long_context_serve_tokens_per_sec_per_chip",
        round(cp_tps, 2),
        "tokens/sec/chip",
        vs_baseline=cp_tps / max(1e-9, off_tps),
        kernels=kernels,
        context_shards=cp,
        ladder={name: n for name, n in ladder},
        per_shard_budget_tokens=budget,
        aggregate_budget_tokens=cp * budget,
        page_size=page_size,
        new_tokens_per_request=n_new,
        ttft_s={k: round(v, 4) for k, v in cp_ttft.items()},
        ttft_top_s=round(cp_ttft[ladder[-1][0]], 4),
        baseline_ttft_s={k: round(v, 4) for k, v in off_ttft.items()},
        baseline_tokens_per_sec=round(off_tps, 2),
        top_rung_unservable_without_cp=1,
        output_parity=1,
        ring_steps=cp_stats["ring_steps"],
        shard_balance=cp_stats["shard_balance"],
        jit_compiles_measured=cp_stats["compiles"],
        steady_state_recompiles=cp_stats["retraces"],
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return cp_tps


def serve_cluster_bench(on_tpu, kernels):
    """Cluster serving (serve/cluster/): N engine replicas behind the
    front-end router on a shared-system-prompt Poisson workload with
    SEVERAL prefix families — the regime where placement matters.

    A/B: prefix-aware routing (longest radix-tree match; least-loaded
    fallback seeds each family on one replica) vs round_robin on the
    SAME arrival schedule and prompts. Per-replica prefix trees are
    sized so ONE replica cannot hold every family: prefix routing
    PARTITIONS the families (each replica serves its own subset at a
    high hit rate), while round-robin smears every family across every
    replica and LRU-thrashes the trees. Reports tokens/sec and TTFT
    p50/p99 for both arms, per-arm cross-replica prefix hit rates,
    placement/affinity counters, and asserts BITWISE output parity
    between the arms (placement must never change tokens — the PR-3
    hit-path guarantee, now load-bearing for routing) plus zero
    steady-state recompiles on EVERY replica under the strict retrace
    sentinel.

    A third mini-run exercises disaggregation: 1 prefill + 1 decode
    replica over a slice of the same workload — prefilled KV pages
    migrate at the chunked-prefill boundary (gather_page_kv →
    scatter_page_kv, byte-exact) — asserting bitwise parity vs the
    prefix arm's outputs for those requests and reporting
    migrations/migrated bytes.

    Measurement caveat (CPU): as with serve_prefix, XLA:CPU steps are
    nearly width-flat, so the throughput gap under-reports the
    accelerator win; the TTFT gap (fewer prefill chunks before the
    first token) and the hit-rate split are the portable signal. Also,
    in-process replicas SHARE the one CPU device — N replicas
    time-slice one chip, so absolute tokens/sec here is not N-way
    scale-out; the A/B ratio at equal resources is the metric."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.serve import ClusterManager, ServingConfig
    from flexflow_tpu.models import llama

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_rep = 2
    n_slots = 16 if on_tpu else 8       # per replica
    # MANY families, FEW requests each — the regime where placement is
    # structural: prefix routing pays ONE cold prefill per family
    # (relatives follow the match), round robin smears each family
    # over every replica and pays a cold prefill per family PER
    # replica, with the duplicated trees also deeper into LRU pressure.
    # n_fam is CO-PRIME with n_rep: an even family count over 2
    # replicas would let round robin (request g -> replica g % 2, g's
    # family = g % n_fam) accidentally partition families perfectly
    # and measure nothing.
    n_fam = 11
    reqs_per_fam = 4 if on_tpu else 3
    n_new = 24 if on_tpu else 8
    sys_len = 128 if on_tpu else 32     # page-aligned shared prefix
    page_size = 64 if on_tpu else 8
    tail_len = 8 if on_tpu else 6
    prefill_chunk = 32 if on_tpu else 8
    if not on_tpu and kernels == "pallas":
        _log("serve_cluster: forcing kernels=xla off-TPU (interpret-mode "
             "pallas would dominate the measurement)")
        kernels = "xla"
    assert sys_len % page_size == 0
    prompt_len = sys_len + tail_len

    def fam_prompt(f, g):
        sys_p = [(j * 11 + f * 41 + 3) % cfg.vocab_size
                 for j in range(sys_len)]
        tail = [(g * 13 + 5 + j * 7) % cfg.vocab_size
                for j in range(tail_len)]
        return sys_p + tail

    # families interleave a full cycle apart: a family's first request
    # has finished prefilling (and published, cache_policy "prefill")
    # by the time its relatives arrive, so routing-time matches see it
    fams = [f for _ in range(reqs_per_fam) for f in range(n_fam)]
    prompts = [fam_prompt(f, g) for g, f in enumerate(fams)]
    n_req = len(prompts)
    # Per-replica pool: a TYPICAL live working set (half the slots at
    # full length — Poisson occupancy rarely pins all slots at once)
    # plus room for about HALF the families' system pages: prefix
    # routing's partition (n_fam/n_rep families per replica) fits,
    # round robin — which wants all n_fam resident on every replica —
    # runs its trees deeper into LRU eviction on top of its doubled
    # cold prefills.
    budget = (
        (n_slots // 2) * (prompt_len + n_new + page_size)
        + (n_fam // 2) * (sys_len + page_size)
    )

    def make_cm(policy, prefill=0, decode=0):
        sc = ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=prefill_chunk,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            max_cached_tokens=budget,
            prefix_caching=True,
            # publish prompts at prefill-final dispatch: the router's
            # match probe then sees a family as soon as its FIRST
            # request finishes prefilling, not its whole generation —
            # concurrent same-family arrivals route (and hit) sooner
            cache_policy="prefill",
            replicas=n_rep,
            router_policy=policy,
            prefill_replicas=prefill,
            decode_replicas=decode,
            # a recompile mid-run would skew the A/B — raise instead
            sanitizers=("retrace",),
        )
        cm = ClusterManager.build(llama, cfg, params, sc)
        # warm every replica's step keys directly (distinct throwaway
        # prompts so no family pre-seeds a tree), then clear the trees
        # and reset counters so both arms start cold and equal
        warm = [
            [(i * 7 + j * 3 + 11) % cfg.vocab_size
             for j in range(prompt_len)]
            for i in range(2)
        ]
        for rep in cm.replicas:
            rep.rm.generate(warm, max_new_tokens=3)
            if rep.rm.prefix_cache is not None:
                rep.rm.prefix_cache.clear()
            rep.rm.stats = type(rep.rm.stats)()
        cm.stats = type(cm.stats)()
        return cm

    def percentiles(vals):
        import numpy as np

        if not vals:
            return 0.0, 0.0
        return (float(np.percentile(vals, 50)), float(np.percentile(vals, 99)))

    def run(cm, arrival_s, workload, sessions=None):
        cids = []
        due = list(zip(arrival_s, enumerate(workload)))
        t0 = time.perf_counter()
        while due or any(not cm._terminal(c) for c in cids):
            now = time.perf_counter() - t0
            while due and due[0][0] <= now:
                _, (i, p) = due.pop(0)
                cids.append(cm.submit(
                    p, max_new_tokens=n_new,
                    session_id=sessions[i] if sessions else None,
                ))
            if not cm.step() and due:
                time.sleep(max(0.0, due[0][0] - (time.perf_counter() - t0)))
        cm.drain()
        wall = time.perf_counter() - t0
        tokens, ttft, outs = 0, [], []
        for c in cids:
            res = cm.result(c)
            assert res.error is None, res.error
            outs.append(list(res.output_tokens))
            tokens += len(res.output_tokens)
            ttft.append(res.profile.ttft_s * 1e3)
        snap = cm.cluster_stats()
        for i, per in enumerate(snap["per_replica"]):
            assert per["retraces"] == 0, (
                f"replica {i}: {per['retraces']} steady-state recompiles"
            )
        return {
            "tps": tokens / wall,
            "ttft": percentiles(ttft),
            "outputs": outs,
            "stats": snap,
        }

    # calibrate offered load on the round-robin arm so both arms face
    # the same sustained churn
    cm_rr = make_cm("round_robin")
    t0 = time.perf_counter()
    cm_rr.generate(prompts[: n_rep * n_slots], max_new_tokens=n_new)
    est_tps = (n_rep * n_slots * n_new) / (time.perf_counter() - t0)
    for rep in cm_rr.replicas:
        if rep.rm.prefix_cache is not None:
            rep.rm.prefix_cache.clear()
        rep.rm.stats = type(rep.rm.stats)()
    cm_rr.stats = type(cm_rr.stats)()
    import numpy as np

    rng = np.random.default_rng(42)
    arrival_s = np.cumsum(
        rng.exponential(scale=n_new / est_tps, size=n_req)
    ).tolist()

    base = run(cm_rr, arrival_s, prompts)
    del cm_rr
    warm = run(make_cm("prefix"), arrival_s, prompts)

    assert warm["outputs"] == base["outputs"], (
        "prefix-aware vs round-robin cluster outputs diverged — "
        "placement must never change tokens"
    )

    # ---- disaggregated mini-run: 1 prefill + 1 decode replica --------
    # per-family session ids model multi-turn chat: repeat requests of
    # a family route by AFFINITY (counted). With ONE prefill replica
    # the placement is unchanged, so parity with the prefix arm holds.
    n_dis = min(n_req, 2 * n_slots)
    cm_dis = make_cm("prefix", prefill=1, decode=1)
    dis = run(cm_dis, arrival_s[:n_dis], prompts[:n_dis],
              sessions=[f"fam-{f}" for f in fams[:n_dis]])
    ds = dis["stats"]
    assert dis["outputs"] == warm["outputs"][:n_dis], (
        "disaggregated outputs diverged from single-pool routing — "
        "page migration must be byte-exact"
    )
    assert ds["migrations"] == n_dis, (
        f"expected {n_dis} migrations, measured {ds['migrations']}"
    )
    cm_dis.check_no_leaks()
    del cm_dis

    s, b = warm["stats"], base["stats"]
    emit(
        "cluster_serve_tokens_per_sec_per_chip",
        round(warm["tps"], 2),
        "tokens/sec/chip",
        vs_baseline=warm["tps"] / max(1e-9, base["tps"]),
        kernels=kernels,
        n_replicas=n_rep,
        n_requests=n_req,
        n_slots_per_replica=n_slots,
        n_families=n_fam,
        new_tokens_per_request=n_new,
        system_prompt_len=sys_len,
        prompt_len=prompt_len,
        page_size=page_size,
        router_policy="prefix",
        placements=s["placements"],
        affinity_hits=ds["affinity_hits"],  # sessions ride the disagg run
        sheds=s["sheds"],
        prefix_hit_rate=s["replicas"]["prefix_hit_rate"],
        prefix_hit_tokens=s["replicas"]["prefix_hit_tokens"],
        rr_prefix_hit_rate=b["replicas"]["prefix_hit_rate"],
        rr_prefix_hit_tokens=b["replicas"]["prefix_hit_tokens"],
        prefix_evictions=s["replicas"]["prefix_evictions"],
        rr_prefix_evictions=b["replicas"]["prefix_evictions"],
        ttft_p50_ms=round(warm["ttft"][0], 1),
        ttft_p99_ms=round(warm["ttft"][1], 1),
        rr_ttft_p50_ms=round(base["ttft"][0], 1),
        rr_ttft_p99_ms=round(base["ttft"][1], 1),
        rr_tokens_per_sec=round(base["tps"], 2),
        disagg_requests=n_dis,
        disagg_migrations=ds["migrations"],
        disagg_migrated_pages=ds["migrated_pages"],
        disagg_migrated_bytes=ds["migrated_bytes"],
        disagg_tokens_per_sec=round(dis["tps"], 2),
        output_parity=1,
        jit_compiles_measured=s["replicas"]["compiles"],
        steady_state_recompiles=s["replicas"]["retraces"],
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return warm["tps"]


def serve_faults_bench(on_tpu, kernels):
    """Fault-tolerant cluster serving (serve/cluster/health.py + faults
    + manager failover): kill one of two replicas mid-Poisson-run with a
    deterministic :class:`FaultPlan` and measure what the users see.

    Two runs on the SAME arrival schedule and prompts: a fault-free
    reference, then a run where replica 1 crashes permanently at a
    replica-local step ~1/3 into its share of the work. The crashed
    replica's in-flight requests fail over to the survivor through
    recompute re-admission, so GREEDY outputs must stay BITWISE the
    reference's — asserted, together with zero hung requests (every
    submission reaches a terminal state inside the wall budget), zero
    errors (the survivor absorbs everything), clean pools and zero
    held slots on survivors, and ZERO steady-state recompiles on every
    replica that never tripped (the failover re-prefills reuse the
    already-compiled step keys).

    Reported: goodput timeline metrics — the DIP (worst post-fault
    completion-goodput bucket over the pre-fault median) and the
    RECOVERY TIME (fault detection until every request that was
    in flight at the fault reached a terminal state) — plus
    failover/retry/health counters and both runs' tokens/sec.

    Measurement caveat (CPU): in-process replicas time-slice one
    device, so losing a replica does NOT halve the hardware — the
    goodput dip here measures the failover machinery's stall (recompute
    re-prefills + the backoff window), not lost capacity; on real
    multi-host the dip adds the capacity loss. Wall-clock bucketing is
    noisy at CPU step rates — dip/recovery are reported, the bitwise
    and zero-hang contracts are what is asserted."""
    import jax
    import numpy as np

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import ClusterManager, ServingConfig
    from flexflow_tpu.serve.cluster import Fault, FaultPlan, HealthState

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_rep = 2
    n_slots = 16 if on_tpu else 8        # per replica
    n_req = 32 if on_tpu else 20
    n_new = 24 if on_tpu else 12
    prompt_len = 48 if on_tpu else 16
    page_size = 64 if on_tpu else 8
    bucket_s = 0.5 if on_tpu else 1.0
    if not on_tpu and kernels == "pallas":
        _log("serve_faults: forcing kernels=xla off-TPU (interpret-mode "
             "pallas would dominate the measurement)")
        kernels = "xla"

    prompts = [
        [(i * 17 + j * 5 + 3) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_req)
    ]

    def make_cm():
        sc = ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=16 if on_tpu else 8,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            replicas=n_rep,
            router_policy="round_robin",
            # a recompile mid-failover would skew goodput — raise instead
            sanitizers=("retrace",),
        )
        cm = ClusterManager.build(llama, cfg, params, sc)
        warm = [
            [(i * 7 + j * 3 + 11) % cfg.vocab_size
             for j in range(prompt_len)]
            for i in range(2)
        ]
        for rep in cm.replicas:
            rep.rm.generate(warm, max_new_tokens=3)
            rep.rm.stats = type(rep.rm.stats)()
        cm.stats = type(cm.stats)()
        return cm

    def run(cm, arrival_s, plan=None):
        injector = cm.attach_faults(plan) if plan is not None else None
        cids = []
        completions = {}          # cid -> (t_done, output tokens)
        terminal_seen = set()
        fault_t = None
        at_fault_inflight = []
        due = list(zip(arrival_s, prompts))
        t0 = time.perf_counter()
        wall_budget = 900.0 if on_tpu else 420.0
        while due or any(not cm._terminal(c) for c in cids):
            now = time.perf_counter() - t0
            # the zero-hung-requests contract: the run must DRAIN
            assert now < wall_budget, (
                f"hung requests: {sum(not cm._terminal(c) for c in cids)}"
                f" non-terminal after {wall_budget}s "
                f"(health={cm.health_snapshot()})"
            )
            while due and due[0][0] <= now:
                _, p = due.pop(0)
                cids.append(cm.submit(p, max_new_tokens=n_new))
            progressed = cm.step()
            if fault_t is None and cm.stats.replica_down > 0:
                fault_t = time.perf_counter() - t0
                at_fault_inflight = [
                    c for c in cids if not cm._terminal(c)
                ]
            for c in cids:
                if c not in terminal_seen and cm._terminal(c):
                    terminal_seen.add(c)
                    completions[c] = (
                        time.perf_counter() - t0,
                        len(cm.requests[c].output_tokens),
                    )
            if not progressed and due:
                time.sleep(max(0.0, due[0][0] - (time.perf_counter() - t0)))
        cm.drain()
        wall = time.perf_counter() - t0
        for c in cids:
            completions.setdefault(
                c, (wall, len(cm.requests[c].output_tokens))
            )
        outs, errors, tokens = [], 0, 0
        for c in cids:
            res = cm.result(c)
            if res.error is not None:
                errors += 1
            outs.append(list(res.output_tokens))
            tokens += len(res.output_tokens)
        if injector is not None:
            injector.release_all()
        cm.check_no_leaks()  # survivors: refcount-clean pools
        for pos, rep in enumerate(cm.replicas):
            if cm.health[pos].state is not HealthState.DOWN:
                assert rep.rm.hold_finished == set(), (
                    f"replica {pos} still holds slots"
                )
            if cm.health[pos].trips == 0:
                assert rep.rm.stats.retraces == 0, (
                    f"survivor replica {pos}: {rep.rm.stats.retraces} "
                    "steady-state recompiles"
                )
        recovery_s = 0.0
        if fault_t is not None and at_fault_inflight:
            recovery_s = max(
                completions[c][0] for c in at_fault_inflight
            ) - fault_t
        # completed-token goodput per wall bucket
        nb = max(1, int(wall // bucket_s) + 1)
        series = [0.0] * nb
        for t_done, toks in completions.values():
            series[min(nb - 1, int(t_done // bucket_s))] += toks / bucket_s
        return {
            "tps": tokens / wall,
            "outs": outs,
            "errors": errors,
            "wall": wall,
            "fault_t": fault_t,
            "recovery_s": recovery_s,
            "series": series,
            "stats": cm.cluster_stats(),
            "health": cm.health_snapshot(),
        }

    # calibrate offered load fault-free, then fix one Poisson schedule
    cm_ref = make_cm()
    t0 = time.perf_counter()
    cm_ref.generate(prompts[:n_slots], max_new_tokens=n_new)
    est_tps = (n_slots * n_new) / (time.perf_counter() - t0)
    for rep in cm_ref.replicas:
        rep.rm.stats = type(rep.rm.stats)()
    cm_ref.stats = type(cm_ref.stats)()
    rng = np.random.default_rng(43)
    arrival_s = np.cumsum(
        rng.exponential(scale=n_new / est_tps, size=n_req)
    ).tolist()

    steps_before = cm_ref.replicas[1].steps_taken
    base = run(cm_ref, arrival_s)
    steps_in_run = cm_ref.replicas[1].steps_taken - steps_before
    del cm_ref
    # kill replica 1 ~1/3 into its (replica-local) share of the run —
    # a fresh cluster's replica steps start at 0, so the fraction of
    # the reference run's count lands mid-flight deterministically
    crash_step = max(5, steps_in_run // 3)
    plan = FaultPlan([Fault("crash", replica=1, step=crash_step)])
    # Observability (flexflow_tpu/obs): the faulted arm additionally
    # records the cluster timeline + arms the flight recorder, and the
    # phase emits the stitched Chrome-trace artifact — the serve-phase
    # timeline ROADMAP item 5c's trace-driven soak consumes. Tracing
    # rides only this arm (host-side dict appends; the asserted
    # contracts are bitwise/zero-hang, not the tps ratio).
    from flexflow_tpu.obs import (
        FlightRecorder,
        attach_observability,
        write_chrome_trace,
    )

    faulted_cm = make_cm()
    recorder = FlightRecorder(capacity=256)
    obs_buf = attach_observability(faulted_cm, recorder=recorder)
    faulted = run(faulted_cm, arrival_s, plan=plan)

    assert base["errors"] == 0 and faulted["errors"] == 0, (
        "failover must absorb a single replica death without a single "
        f"failed request (base={base['errors']}, "
        f"faulted={faulted['errors']})"
    )
    assert faulted["outs"] == base["outs"], (
        "failed-over greedy outputs diverged from the fault-free run — "
        "recompute re-admission must be bitwise"
    )
    fs = faulted["stats"]
    assert fs["replica_down"] >= 1 and fs["failovers"] >= 1, (
        f"the fault did not fire as scripted: {fs}"
    )

    # timeline artifact: one stitched Chrome/Perfetto trace of the
    # faulted run (replica lanes + router lane; failover/health events
    # included) + the crashed replica's flight-recorder post-mortem
    trace_path = os.path.join(
        os.environ.get("BENCH_TRACE_DIR", "."),
        "BENCH_trace_serve_faults.json",
    )
    doc = write_chrome_trace(trace_path, obs_buf)
    down_dumps = recorder.dumps_for("replica1")
    assert down_dumps, (
        "the crashed replica tripped DOWN but the flight recorder "
        "captured no post-mortem dump"
    )
    lanes = sorted({e.get("lane", "") for e in obs_buf.events})
    emit(
        "faults_serve_trace_events",
        len(doc["traceEvents"]),
        "events",
        kernels=kernels,
        path=trace_path,
        lanes=lanes,
        flight_recorder_dumps=len(recorder.dumps),
        down_dump_final_event=down_dumps[0]["events"][-1]["name"],
        platform=_platform(),
    )

    # goodput dip: worst post-fault bucket over the pre-fault median
    dip_ratio = 1.0
    if faulted["fault_t"] is not None:
        fb = int(faulted["fault_t"] // bucket_s)
        pre = [g for g in faulted["series"][:fb] if g > 0]
        post = faulted["series"][fb:] or [0.0]
        if pre:
            dip_ratio = min(post) / float(np.median(pre))

    emit(
        "faults_serve_tokens_per_sec_per_chip",
        round(faulted["tps"], 2),
        "tokens/sec/chip",
        vs_baseline=faulted["tps"] / max(1e-9, base["tps"]),
        kernels=kernels,
        n_replicas=n_rep,
        n_requests=n_req,
        n_slots_per_replica=n_slots,
        new_tokens_per_request=n_new,
        crash_step=crash_step,
        goodput_dip_ratio=round(dip_ratio, 4),
        recovery_time_s=round(faulted["recovery_s"], 3),
        fault_time_s=(
            round(faulted["fault_t"], 3) if faulted["fault_t"] else None
        ),
        failovers=fs["failovers"],
        retries=fs["retries"],
        replica_down=fs["replica_down"],
        probes=fs["probes"],
        step_faults=fs["step_faults"],
        failover_errors=fs["failover_errors"],
        hung_requests=0,
        errors=faulted["errors"],
        health_at_end=faulted["health"],
        fault_free_tokens_per_sec=round(base["tps"], 2),
        output_parity=1,
        steady_state_recompiles=0,
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return faulted["tps"]


def serve_elastic_bench(on_tpu, kernels):
    """Elastic, crash-recoverable control plane (serve/cluster/
    journal.py + reconfigure.py + ClusterManager.recover): Poisson
    traffic through a LIVE scale 2→3→2 — a replica joins mid-run
    (scale_out) and later drains back out (scale_in) — plus a scripted
    MANAGER death (FaultPlan "manager_crash") recovered from the
    durable request journal mid-traffic.

    Two runs on the SAME arrival schedule and prompts: a static
    2-replica reference, then the elastic run. ASSERTED: zero lost
    requests and zero errors (every submission reaches a terminal
    state through the restart), greedy outputs BITWISE the static
    run's (scale_out/scale_in/set_pools placements and the journal
    recovery's recompute re-admissions move WHERE tokens are computed,
    never WHICH tokens), scale_outs == scale_ins == 1 with the retired
    replica leak-free, manager_recoveries == 1, and zero steady-state
    recompiles on replicas that lived through the whole run.

    Reported: manager recovery time (crash → every stranded request
    terminal) + the recover() rebuild time, drain time (begin_scale_in
    → retire), journal bytes/records per request, and both runs'
    tokens/sec.

    Measurement caveat (CPU): in-process replicas time-slice one
    device, so the scale events do not change hardware capacity here —
    recovery/drain times measure the CONTROL PLANE's cost (journal
    replay, engine rebuild, recompute re-admission), which is the
    number the item-2b autoscaler budgets against; on real multi-host
    the capacity change adds on top."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import ClusterManager, ServingConfig
    from flexflow_tpu.serve.cluster import Fault, FaultPlan
    from flexflow_tpu.serve.cluster.faults import InjectedManagerCrash

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 16 if on_tpu else 8        # per replica
    n_req = 30 if on_tpu else 18
    n_new = 24 if on_tpu else 12
    prompt_len = 48 if on_tpu else 16
    page_size = 64 if on_tpu else 8
    if not on_tpu and kernels == "pallas":
        _log("serve_elastic: forcing kernels=xla off-TPU")
        kernels = "xla"

    prompts = [
        [(i * 13 + j * 7 + 5) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_req)
    ]
    journal_dir = tempfile.mkdtemp(prefix="ffelastic_")

    def sc(journal=False):
        return ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=16 if on_tpu else 8,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            replicas=2,
            router_policy="round_robin",
            journal_dir=journal_dir if journal else None,
            sanitizers=("retrace",),
        )

    def make_cm(journal=False):
        cm = ClusterManager.build(llama, cfg, params, sc(journal))
        warm = [
            [(i * 7 + j * 3 + 11) % cfg.vocab_size
             for j in range(prompt_len)]
            for i in range(2)
        ]
        for rep in cm.replicas:
            rep.rm.generate(warm, max_new_tokens=3)
            rep.rm.stats = type(rep.rm.stats)()
        cm.stats = type(cm.stats)()
        return cm

    # --- static reference arm (also calibrates the Poisson schedule)
    cm_ref = make_cm()
    t0 = time.perf_counter()
    cm_ref.generate(prompts[:n_slots], max_new_tokens=n_new)
    est_tps = (n_slots * n_new) / (time.perf_counter() - t0)
    for rep in cm_ref.replicas:
        rep.rm.stats = type(rep.rm.stats)()
    cm_ref.stats = type(cm_ref.stats)()
    rng = np.random.default_rng(47)
    arrival_s = np.cumsum(
        rng.exponential(scale=n_new / est_tps, size=n_req)
    ).tolist()

    def run_static(cm):
        cids, due = [], list(zip(arrival_s, prompts))
        t0 = time.perf_counter()
        while due or any(not cm._terminal(c) for c in cids):
            now = time.perf_counter() - t0
            assert now < (900.0 if on_tpu else 420.0), "static arm hung"
            while due and due[0][0] <= now:
                _, p = due.pop(0)
                cids.append(cm.submit(p, max_new_tokens=n_new))
            if not cm.step() and due:
                time.sleep(max(0.0, due[0][0] - (time.perf_counter() - t0)))
        cm.drain()
        wall = time.perf_counter() - t0
        outs = [list(cm.result(c).output_tokens) for c in cids]
        return outs, sum(len(o) for o in outs) / wall

    steps_before = cm_ref._step_counter
    ref_outs, ref_tps = run_static(cm_ref)
    ref_steps = cm_ref._step_counter - steps_before
    errors_ref = sum(
        1 for c in cm_ref.requests if cm_ref.result(c).error is not None
    )
    del cm_ref

    # --- elastic arm: scale out at 1/4 submitted, drain the newcomer
    # back out at 3/4 submitted, manager dies mid-run and recovers
    crash_step = max(8, ref_steps // 2)
    plan = FaultPlan([Fault("manager_crash", replica=0, step=crash_step)])
    cm = make_cm(journal=True)
    injector = cm.attach_faults(plan)
    scale_out_at = max(1, n_req // 4)
    scale_in_at = max(2, (3 * n_req) // 4)
    cids, due = [], list(zip(arrival_s, prompts))
    scaled_out = drain_begun = False
    t_drain0 = t_drain1 = None
    t_crash = recover_build_s = None
    at_crash_inflight, completions = [], {}
    jbytes_before_crash = jrecs_before_crash = 0
    recoveries = 0
    t0 = time.perf_counter()
    wall_budget = 900.0 if on_tpu else 420.0
    while due or any(not cm._terminal(c) for c in cids):
        now = time.perf_counter() - t0
        assert now < wall_budget, (
            f"hung requests after {wall_budget}s "
            f"(health={cm.health_snapshot()})"
        )
        while due and due[0][0] <= now:
            _, p = due.pop(0)
            cids.append(cm.submit(p, max_new_tokens=n_new))
        if not scaled_out and len(cids) >= scale_out_at:
            cm.scale_out(warm=True)
            scaled_out = True
        if scaled_out and not drain_begun and len(cids) >= scale_in_at:
            cm.begin_scale_in(2)
            t_drain0 = time.perf_counter()
            drain_begun = True
        try:
            progressed = cm.step()
        except InjectedManagerCrash:
            # the scripted kill -9: drop the manager object (everything
            # in-process dies with it) and restart from the journal —
            # the SAME injector re-attaches so the crash stays consumed
            t_crash = time.perf_counter()
            at_crash_inflight = [c for c in cids if not cm._terminal(c)]
            jbytes_before_crash = cm.stats.journal_bytes
            jrecs_before_crash = cm.stats.journal_records
            was_draining = bool(cm._draining)
            del cm
            cm = ClusterManager.recover(llama, cfg, params, sc(journal=True))
            recover_build_s = time.perf_counter() - t_crash
            cm.attach_faults(injector)
            recoveries += 1
            if was_draining and len(cm.replicas) > 2:
                # the drain had begun but not committed — re-issue it
                # (recovery replays committed membership only)
                cm.begin_scale_in(2)
            continue
        if drain_begun and t_drain1 is None and len(cm.replicas) == 2:
            t_drain1 = time.perf_counter()
        for c in cids:
            if c not in completions and cm._terminal(c):
                completions[c] = time.perf_counter() - t0
        if not progressed and due:
            time.sleep(max(0.0, due[0][0] - (time.perf_counter() - t0)))
    cm.drain()
    wall = time.perf_counter() - t0
    if t_drain1 is None and len(cm.replicas) == 2:
        t_drain1 = time.perf_counter()
    for c in cids:
        completions.setdefault(c, wall)
    outs = [list(cm.result(c).output_tokens) for c in cids]
    errors = sum(1 for c in cids if cm.result(c).error is not None)
    tps = sum(len(o) for o in outs) / wall

    st = cm.cluster_stats()
    assert errors == 0 and errors_ref == 0, (
        f"elastic serving lost requests (static={errors_ref}, "
        f"elastic={errors})"
    )
    assert len(outs) == n_req, "a submission vanished across the restart"
    assert outs == ref_outs, (
        "elastic outputs diverged from the static-membership run — "
        "reconfiguration/recovery must be bitwise"
    )
    assert recoveries == 1 and st["manager_recoveries"] == 1, (
        f"the manager crash did not fire/recover as scripted: {st}"
    )
    # scale events split across manager incarnations (stats are
    # per-incarnation; the journal carries membership across) — the
    # membership itself is the cross-incarnation assertion:
    assert scaled_out and drain_begun
    assert len(cm.replicas) == 2, (
        f"scale_in never retired the newcomer ({len(cm.replicas)} "
        "replicas at end)"
    )
    cm.check_no_leaks()
    for rep in cm.replicas:
        assert rep.rm.hold_finished == set()
        assert rep.rm.stats.retraces == 0, (
            f"replica {rep.index}: steady-state recompiles"
        )
    recovery_s = 0.0
    if t_crash is not None and at_crash_inflight:
        recovery_s = max(
            completions[c] for c in at_crash_inflight
        ) - (t_crash - t0)
    drain_s = (
        (t_drain1 - t_drain0)
        if t_drain0 is not None and t_drain1 is not None else 0.0
    )
    journal_bytes = jbytes_before_crash + st["journal_bytes"]
    journal_records = jrecs_before_crash + st["journal_records"]
    shutil.rmtree(journal_dir, ignore_errors=True)

    emit(
        "elastic_serve_tokens_per_sec_per_chip",
        round(tps, 2),
        "tokens/sec/chip",
        vs_baseline=tps / max(1e-9, ref_tps),
        kernels=kernels,
        n_requests=n_req,
        n_slots_per_replica=n_slots,
        new_tokens_per_request=n_new,
        schedule="2->3->2 + manager kill/restart",
        crash_step=crash_step,
        manager_recovery_time_s=round(recovery_s, 3),
        recover_build_time_s=round(recover_build_s or 0.0, 3),
        drain_time_s=round(drain_s, 3),
        journal_bytes=journal_bytes,
        journal_records=journal_records,
        journal_bytes_per_request=round(journal_bytes / n_req, 1),
        journal_replayed=st["journal_replayed"],
        scale_outs_after_recovery=st["scale_outs"],
        scale_ins=st["scale_ins"],
        manager_recoveries=st["manager_recoveries"],
        failovers=st["failovers"],
        errors=0,
        lost_requests=0,
        output_parity=1,
        steady_state_recompiles=0,
        static_tokens_per_sec=round(ref_tps, 2),
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return tps


def serve_autotune_bench(on_tpu, kernels):
    """Self-driving serving (serve/autotune/): (a) does the analytical
    serving cost model RANK real configurations correctly, and (b) does
    the live journaled autoscaler actually drive the PR-14 elastic
    control plane under a traffic burst.

    Part (a) measures a 6-rung config ladder — replicas (1/2) ×
    kv_quant (fp/int8/int4) × speculation (early-exit self-draft) — as
    closed-loop saturated tokens/sec on warmed clusters, prices the
    same candidates through ServingCostModel, and ASSERTS Spearman
    rank correlation >= 0.7 between predicted capacity and measured
    throughput. Off-chip the chip constants are measured directly
    (a timed matmul for FLOP/s, a timed elementwise stream for
    bytes/s): calibrate_chip's [0.05, 8.0] efficiency clamp floors
    BOTH fractions on a CPU host, which would preserve the TPU's
    ~240 flops/byte roofline ratio on a ~3 flops/byte box — the
    dequant-FLOP tax on quantized KV would vanish from predictions
    exactly where the measurement pays it, inverting the quantized
    rungs. Predictions off-chip are RANKED, never absolute (the
    README caveat); the ratio is what must be honest.

    Part (b) runs the same burst trace twice — a static 1-replica arm,
    then an autoscale="drive" arm whose cost model is throughput-
    calibrated from the static arm (predicted fp capacity == measured
    tokens/sec, the absolute anchor ranking alone cannot give).
    ASSERTED: the autoscaler fires >= 1 journaled scale_out AND the
    matching drain-based scale_in (decisions ordered out-before-in,
    the newcomer retired by the end), zero errors, outputs BITWISE the
    static arm's (the policy moves WHERE tokens are computed, never
    WHICH), the journal carries the autoscale audit records, and zero
    steady-state recompiles on the untouched original replica.
    Reported: TTFT p99 per arm (wall clock — on CPU the replicas
    time-slice one device, so the A/B measures the CONTROL PLANE, not
    a capacity change) and the recovery span in cluster steps between
    the scale_out and scale_in decisions. The offline search rides
    along: search_serving_config must emit a validate_cluster-clean
    config for the same geometry."""
    import dataclasses as _dc
    import math
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from flexflow_tpu.models import llama
    from flexflow_tpu.search.machine_model import TPUChip, calibrate_chip
    from flexflow_tpu.serve import ClusterManager, ServingConfig, SpecConfig
    from flexflow_tpu.serve.autotune import (
        ModelGeometry,
        ServingCandidate,
        ServingCostModel,
        TrafficEstimator,
        TrafficProfile,
        search_serving_config,
    )

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 16 if on_tpu else 8
    n_new = 16 if on_tpu else 10
    prompt_len = 48 if on_tpu else 16
    page_size = 64 if on_tpu else 8
    chunk = 16 if on_tpu else 8
    slo_ttft_s = 0.5
    if not on_tpu and kernels == "pallas":
        _log("serve_autotune: forcing kernels=xla off-TPU")
        kernels = "xla"

    geom = ModelGeometry.from_model_config(cfg)

    # -- chip constants: calibrated roofline on the chip, measured
    # from scratch on a host (see docstring for why not calibrate_chip)
    if on_tpu:
        chip = calibrate_chip(TPUChip.v5e())
    else:
        n = 512
        a = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
        mm = jax.jit(lambda x: x @ x)
        mm(a).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(8):
            out = mm(a)
        out.block_until_ready()
        host_flops = 8 * 2.0 * n ** 3 / (time.perf_counter() - t0)
        v = jnp.ones((4 << 20,), jnp.float32)   # 16 MB in, 16 MB out
        stream = jax.jit(lambda x: x * 1.0001 + 2.0)
        stream(v).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(8):
            out = stream(v)
        out.block_until_ready()
        host_bw = 8 * 2.0 * v.nbytes / (time.perf_counter() - t0)
        chip = TPUChip(
            name="host", bf16_flops=host_flops, hbm_bandwidth=host_bw,
            hbm_capacity=4 << 30, ici_bandwidth=1e9,
            mxu_efficiency=1.0, hbm_efficiency=1.0,
        )
        _log(
            f"serve_autotune host roofline: {host_flops / 1e9:.1f} "
            f"GFLOP/s, {host_bw / 1e9:.1f} GB/s "
            f"({host_flops / host_bw:.1f} flops/byte)"
        )
    cost_model = ServingCostModel(geom, chip=chip)

    prompts = [
        [(i * 13 + j * 7 + 5) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(2 * n_slots)
    ]
    warm = [
        [(i * 7 + j * 3 + 11) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(2)
    ]

    def make_sc(replicas, kv_quant, journal_dir=None, autoscale=None):
        auto = {}
        if autoscale:
            auto = dict(
                autoscale=autoscale,
                slo_ttft_s=slo_ttft_s,
                autoscale_min_replicas=1,
                autoscale_max_replicas=2,
                autoscale_cooldown_steps=8,
            )
        return ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=chunk,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            kv_quant=kv_quant,
            replicas=replicas,
            router_policy="round_robin",
            journal_dir=journal_dir,
            sanitizers=("retrace",),
            **auto,
        )

    def make_cm(sc, spec=None):
        cm = ClusterManager.build(llama, cfg, params, sc, spec=spec)
        for rep in cm.replicas:
            rep.rm.generate(warm, max_new_tokens=3)
            rep.rm.stats = type(rep.rm.stats)()
        cm.stats = type(cm.stats)()
        return cm

    wall_budget = 900.0 if on_tpu else 420.0

    # ---- part (a): the measured config ladder vs predicted capacity.
    # Every rung is SATURATED (replicas × n_slots requests, so each
    # replica runs a full batch) and both sides rank PER CHIP — the
    # search's own objective (tokens/sec/chip): on a time-sliced host
    # two full replicas measure ~one replica's aggregate rate, so
    # aggregate-vs-aggregate would rank on near-ties; per chip the
    # replicas=2 rungs are decisively lower on both sides.
    def run_rung(replicas, kv_quant, spec):
        cm = make_cm(make_sc(replicas, kv_quant), spec=spec)
        t0 = time.perf_counter()
        cids = [
            cm.submit(p, max_new_tokens=n_new)
            for p in prompts[:replicas * n_slots]
        ]
        while any(not cm._terminal(c) for c in cids):
            assert time.perf_counter() - t0 < wall_budget, "rung hung"
            if not cm.step():
                cm.drain()
        cm.drain()
        wall = time.perf_counter() - t0
        toks = acc = drafted = 0
        for c in cids:
            res = cm.result(c)
            assert res.error is None, f"rung error: {res.error}"
            toks += len(res.output_tokens)
            acc += res.profile.accepted_tokens
            drafted += res.profile.speculated_tokens
        del cm
        return toks / wall, (acc / drafted if drafted else 0.0)

    ladder = [
        ("fp_r1", 1, None, False),
        ("fp_r2", 2, None, False),
        ("int8_r1", 1, "int8", False),
        ("int8_r2", 2, "int8", False),
        ("int4_r1", 1, "int4", False),
        ("spec_r1", 1, None, True),
    ]
    measured, predicted, rows = [], [], []
    for name, reps, quant, spec_on in ladder:
        spec = (
            SpecConfig(
                beam_width=2, beam_depth=4,
                draft="early_exit", draft_layers=1,
            )
            if spec_on else None
        )
        tps, accept = run_rung(reps, quant, spec)
        cand = ServingCandidate(
            replicas=reps,
            page_size=page_size,
            kv_quant=quant,
            speculation=spec_on,
            spec_width=2,
            spec_depth=4,
            whole_step=False,
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=chunk,
        )
        traffic = TrafficProfile(
            arrival_rate_rps=1e9,    # saturated: rank by pure capacity
            prompt_len_p50=float(prompt_len),
            prompt_len_p99=float(prompt_len),
            output_len_p50=float(n_new),
            output_len_p99=float(n_new),
            prefix_share=0.0,
            spec_accept_rate=accept,
        )
        pred = cost_model.predict(
            cand, traffic,
            # in-process replicas time-slice ONE device off-chip
            oversubscription=1.0 if on_tpu else float(reps),
        )
        measured.append(tps / cand.chips)
        predicted.append(pred.capacity_tokens_per_s / cand.chips)
        rows.append({
            "config": name,
            "measured_tokens_per_sec_per_chip": round(tps / cand.chips, 2),
            "predicted_capacity_per_chip": round(
                pred.capacity_tokens_per_s / cand.chips, 2),
            "spec_accept_rate": round(accept, 3),
        })
        _log(
            f"serve_autotune rung {name}: measured {tps / cand.chips:.1f} "
            f"tok/s/chip, predicted capacity "
            f"{pred.capacity_tokens_per_s / cand.chips:.1f}"
        )

    def _ranks(vals):
        order = sorted(range(len(vals)), key=lambda i: vals[i])
        out = [0.0] * len(vals)
        i = 0
        while i < len(order):
            j = i
            while (j + 1 < len(order)
                   and vals[order[j + 1]] == vals[order[i]]):
                j += 1
            for k in range(i, j + 1):
                out[order[k]] = (i + j) / 2.0 + 1.0
            i = j + 1
        return out

    rx, ry = _ranks(measured), _ranks(predicted)
    mx, my = sum(rx) / len(rx), sum(ry) / len(ry)
    cov = sum((x - mx) * (y - my) for x, y in zip(rx, ry))
    vx = sum((x - mx) ** 2 for x in rx)
    vy = sum((y - my) ** 2 for y in ry)
    rank_corr = cov / math.sqrt(vx * vy) if vx > 0 and vy > 0 else 0.0
    assert rank_corr >= 0.7, (
        f"cost model ranks the measured ladder wrong "
        f"(spearman={rank_corr:.3f}): {rows}"
    )

    # -- the offline search rides along: it must emit a runnable config
    best, report = search_serving_config(
        geom,
        TrafficProfile(
            arrival_rate_rps=max(10.0, measured[0] / max(1, n_new)),
            prompt_len_p50=float(prompt_len),
            prompt_len_p99=float(2 * prompt_len),
            output_len_p50=float(n_new),
            output_len_p99=float(2 * n_new),
        ),
        chip_budget=4,
        cost_model=cost_model,
        max_requests_per_batch=n_slots,
        max_sequence_length=prompt_len + n_new + 8,
    )
    assert best is not None, f"search found nothing: {report.summary()}"
    best.to_serving_config().validate_cluster()

    # ---- part (b): burst A/B — static arm, then the live autoscaler
    burst_wave = 2                       # submissions per cluster step
    burst_steps = 20
    n_burst = burst_wave * burst_steps
    bprompts = [
        [(i * 11 + j * 5 + 3) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_burst)
    ]

    def run_burst(cm):
        cids, submitted = [], 0
        t0 = time.perf_counter()
        while submitted < n_burst or any(not cm._terminal(c) for c in cids):
            assert time.perf_counter() - t0 < wall_budget, "burst arm hung"
            for _ in range(burst_wave):
                if submitted >= n_burst:
                    break
                cids.append(
                    cm.submit(bprompts[submitted], max_new_tokens=n_new)
                )
                submitted += 1
            if not cm.step():
                cm.drain()
        cm.drain()
        wall = time.perf_counter() - t0
        outs = [list(cm.result(c).output_tokens) for c in cids]
        errors = sum(1 for c in cids if cm.result(c).error is not None)
        ttft = sorted(cm.result(c).profile.ttft_s for c in cids)
        return outs, errors, ttft, sum(map(len, outs)) / wall

    cm_s = make_cm(make_sc(1, None))
    ref_outs, ref_errors, ref_ttft, ref_tps = run_burst(cm_s)
    assert ref_errors == 0
    del cm_s

    # absolute anchor: scale the roofline so the predicted fp_r1
    # capacity equals this box's MEASURED saturated tokens/sec — the
    # thresholds the policy compares against SLOs need absolute
    # numbers, which the ranked-only host roofline cannot give
    scale = measured[0] / max(1e-9, predicted[0])
    eff_chip = _dc.replace(
        chip,
        bf16_flops=chip.bf16_flops * scale,
        hbm_bandwidth=chip.hbm_bandwidth * scale,
    )

    journal_dir = tempfile.mkdtemp(prefix="ffautotune_")
    cm = make_cm(make_sc(
        1, None, journal_dir=journal_dir, autoscale="drive",
    ))
    auto = cm.autoscaler
    auto.cost_model = ServingCostModel(geom, chip=eff_chip)
    auto.estimator = TrafficEstimator(warmup_steps=4)
    auto.eval_interval_steps = 2
    auto.breach_evals = 2
    auto.clear_evals = 2
    auto.cooldown_steps = 8

    outs, errors, ttft, tps = run_burst(cm)
    # idle-step until the drain-based scale_in COMMITS (retires the
    # newcomer) — begin_scale_in fires inside the drive loop, the
    # retirement lands at a later step's sweep
    idle = 0
    while (cm.stats.scale_ins < 1 or len(cm.replicas) > 1) and idle < 600:
        cm.step()
        idle += 1

    st = cm.cluster_stats()
    decisions = list(auto.decisions)
    applied = [d for d in decisions if d.applied]
    out_steps = [d.step for d in applied if d.kind == "scale_out"]
    in_steps = [d.step for d in applied if d.kind == "scale_in"]
    assert errors == 0, f"autoscale arm errors: {errors}"
    assert outs == ref_outs, (
        "autoscaled outputs diverged from the static arm — the policy "
        "must move WHERE tokens are computed, never WHICH"
    )
    assert st["scale_outs"] >= 1 and st["scale_ins"] >= 1, (
        f"the burst did not drive a full scale_out->scale_in cycle: "
        f"{st['scale_outs']}/{st['scale_ins']} "
        f"(decisions={[(d.kind, d.step, d.reason) for d in decisions]})"
    )
    assert out_steps and in_steps and min(out_steps) < min(in_steps), (
        f"decisions out of order: out={out_steps} in={in_steps}"
    )
    assert len(cm.replicas) == 1, (
        f"scale_in never retired the newcomer "
        f"({len(cm.replicas)} replicas at end)"
    )
    cm.check_no_leaks()
    rep0 = cm.replicas[0]
    assert rep0.index == 0 and rep0.rm.stats.retraces == 0, (
        "steady-state recompiles on the untouched original replica"
    )
    with open(cm.journal.path, "rb") as f:
        raw = f.read()
    assert b"autoscale" in raw, (
        "autoscale decisions missing from the durable journal"
    )
    recovery_steps = min(in_steps) - min(out_steps)
    del cm
    shutil.rmtree(journal_dir, ignore_errors=True)

    def p99(vals):
        return vals[int(0.99 * (len(vals) - 1))] if vals else 0.0

    emit(
        "autotune_serve_tokens_per_sec_per_chip",
        round(tps, 2),
        "tokens/sec/chip",
        vs_baseline=tps / max(1e-9, ref_tps),
        kernels=kernels,
        rank_corr=round(rank_corr, 3),
        n_configs=len(ladder),
        ladder=rows,
        chip_name=chip.name,
        chip_flops_per_byte=round(chip.bf16_flops / chip.hbm_bandwidth, 2),
        capacity_anchor_scale=round(scale, 4),
        search_evaluated=report.evaluated,
        search_pruned=report.pruned,
        search_best_chips=best.chips,
        search_best_replicas=best.replicas,
        search_best_kv_quant=best.kv_quant,
        search_summary=report.summary().splitlines()[0],
        burst_requests=n_burst,
        new_tokens_per_request=n_new,
        scale_outs=st["scale_outs"],
        scale_ins=st["scale_ins"],
        autoscale_decisions=len(decisions),
        autoscale_recovery_steps=recovery_steps,
        ttft_p99_static_s=round(p99(ref_ttft), 3),
        ttft_p99_autoscaled_s=round(p99(ttft), 3),
        static_tokens_per_sec=round(ref_tps, 2),
        errors=0,
        output_parity=1,
        steady_state_recompiles=0,
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return tps


def serve_transport_bench(on_tpu, kernels):
    """Multi-host cluster transport (serve/cluster/transport.py +
    remote.py): a LOOPBACK-transported cluster — every Replica call
    round-trips the length-prefixed binary wire codec — with warm
    standbys, under a replica death.

    Two runs on the SAME prefix-family workload: (a) WARM — one
    standby; on the DOWN transition it adopts the dead replica's radix
    tree (block keys + page bytes over the transport) and its routing
    position, so post-failover requests from the adopted families hit
    the prefix cache immediately; (b) COLD — no standby; survivors
    re-seed those families from scratch. ASSERTED: the warm arm's
    post-failover hit rate on the dead replica's families is > 0 AND
    strictly above the cold arm's, every request terminal with zero
    errors in both arms, outputs bitwise across arms (placement moves,
    greedy tokens must not), standby_adoptions == 1, and ZERO
    steady-state recompiles on every replica that never tripped
    (strict retrace sanitizer). Reported: post-failover hit rates,
    tokens/sec both arms, wire bytes both ways, rpc error/retry/
    heartbeat-gap counters and migrated tree size.

    Measurement caveat (CPU): loopback replicas time-slice one device,
    so tokens/sec measures transport + failover overhead at parity
    scale, not multi-host capacity; the hit-rate A/B and the wire-byte
    accounting are platform-independent signals."""
    import time as _time

    import jax
    import numpy as np

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import ClusterManager, ServingConfig
    from flexflow_tpu.serve.cluster import Fault, FaultPlan, HealthState

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 16 if on_tpu else 8
    n_new = 16 if on_tpu else 8
    prompt_len = 48 if on_tpu else 20
    page_size = 64 if on_tpu else 8
    n_families = 5
    wall_budget = 900.0 if on_tpu else 420.0
    if not on_tpu and kernels == "pallas":
        _log("serve_transport: forcing kernels=xla off-TPU")
        kernels = "xla"

    def family_prompt(fid, j):
        head = [(fid * 101 + 5 + k) % cfg.vocab_size
                for k in range(prompt_len - 6)]
        return head + [(j * 13 + k) % cfg.vocab_size for k in range(6)]

    # seed in TWO sequential waves: wave A misses everywhere and
    # least-loaded spreads the families across the replicas (the
    # partition), wave B prefix-routes each family to its seeding
    # replica — one replica per family, so the cold arm's survivors
    # genuinely do NOT hold the victim's families
    seed_wave_a = [family_prompt(f, 0) for f in range(n_families)]
    seed_wave_b = [family_prompt(f, 1) for f in range(n_families)]
    main_wave = [family_prompt(f, 2) for f in range(n_families)]

    def run(standby):
        sc = ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=16 if on_tpu else 8,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            prefix_caching=True,
            replicas=2,
            router_policy="prefix",
            replica_transport="loopback",
            standby_replicas=1 if standby else 0,
            sanitizers=("retrace",),
        )
        cm = ClusterManager.build(llama, cfg, params, sc)
        t0 = _time.perf_counter()
        cm.generate(seed_wave_a, max_new_tokens=n_new)
        cm.generate(seed_wave_b, max_new_tokens=n_new)
        scores = [
            sum(rep.prefix_score(family_prompt(f, 3))
                for f in range(n_families))
            for rep in cm.replicas
        ]
        victim = max(range(2), key=lambda i: scores[i])
        victim_families = [
            f for f in range(n_families)
            if cm.replicas[victim].prefix_score(family_prompt(f, 3)) > 0
        ]
        cm.attach_faults(FaultPlan([Fault(
            "crash", replica=victim,
            step=cm.replicas[victim].steps_taken + 2,
        )]))
        cids = [cm.submit(p, max_new_tokens=n_new) for p in main_wave]
        while not cm.stats.replica_down:
            assert _time.perf_counter() - t0 < wall_budget, "fault never fired"
            cm.step()
        # POST-FAILOVER wave from the dead replica's families — the
        # warm-vs-cold measurement: do these hit the prefix cache?
        post = [
            cm.submit(family_prompt(f, 4 + j), max_new_tokens=n_new)
            for f in victim_families for j in range(2)
        ]
        cids += post
        while any(not cm._terminal(c) for c in cids):
            assert _time.perf_counter() - t0 < wall_budget, (
                f"hung requests (health={cm.health_snapshot()})"
            )
            if not cm.step():
                break
        cm.drain()
        wall = _time.perf_counter() - t0
        results = [cm.result(c) for c in cids]
        errors = sum(1 for r in results if r.error is not None)
        tokens = sum(len(r.output_tokens) for r in results)
        post_hits = [
            cm.result(c).profile.cached_prefix_len > 0 for c in post
        ]
        for pos, rep in enumerate(cm.replicas):
            if (
                cm.health[pos].state is not HealthState.DOWN
                and cm.health[pos].trips == 0
            ):
                assert rep.rm.stats.retraces == 0, (
                    f"replica {pos}: {rep.rm.stats.retraces} steady-state "
                    "recompiles"
                )
        if cm.fault_injector is not None:
            cm.fault_injector.release_all()
        cm.check_no_leaks()
        return {
            "outs": [list(r.output_tokens) for r in results],
            "errors": errors,
            "tps": tokens / wall,
            "post_hit_rate": (
                sum(post_hits) / len(post_hits) if post_hits else 0.0
            ),
            "victim_families": len(victim_families),
            "stats": cm.cluster_stats(),
        }

    warm = run(standby=True)
    cold = run(standby=False)

    assert warm["errors"] == 0 and cold["errors"] == 0, (
        f"failover must absorb the death (warm={warm['errors']}, "
        f"cold={cold['errors']})"
    )
    assert warm["outs"] == cold["outs"], (
        "greedy outputs must not depend on standby placement"
    )
    assert warm["stats"]["standby_adoptions"] == 1, warm["stats"]
    assert warm["post_hit_rate"] > 0.0, (
        "warm-standby adoption produced ZERO post-failover prefix hits "
        "— the adopted families should be hot immediately"
    )
    assert warm["post_hit_rate"] > cold["post_hit_rate"], (
        f"warm adoption ({warm['post_hit_rate']}) must beat cold "
        f"re-seed ({cold['post_hit_rate']})"
    )
    ws = warm["stats"]
    emit(
        "transport_standby_warm_hit_rate",
        round(warm["post_hit_rate"], 4),
        "fraction",
        vs_baseline=(
            warm["post_hit_rate"] / cold["post_hit_rate"]
            if cold["post_hit_rate"] else None
        ),
        kernels=kernels,
        cold_reseed_hit_rate=round(cold["post_hit_rate"], 4),
        victim_families=warm["victim_families"],
        standby_adoptions=ws["standby_adoptions"],
        warm_tokens_per_sec=round(warm["tps"], 2),
        cold_tokens_per_sec=round(cold["tps"], 2),
        wire_bytes_sent=ws["wire_bytes_sent"],
        wire_bytes_received=ws["wire_bytes_received"],
        rpc_errors=ws["rpc_errors"],
        rpc_retries=ws["rpc_retries"],
        heartbeat_gaps=ws["heartbeat_gaps"],
        reconnects=ws["reconnects"],
        replica_down=ws["replica_down"],
        failovers=ws["failovers"],
        output_parity=1,
        errors=0,
        steady_state_recompiles=0,
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return warm["post_hit_rate"]


def serve_cluster_async_bench(on_tpu, kernels):
    """Concurrent cluster stepping (serve/cluster/transport.py
    multiplexed call-tag RPCs + manager.py fan-out drive loop,
    ``ServingConfig.concurrent_stepping``): N=3 loopback replicas
    behind THREADED transports with an injected per-RPC link delay d —
    the regime where the wire, not the compute, dominates a cluster
    step.

    Two arms on the SAME prompts: (a) SERIAL — the pre-multiplexing
    drive loop blocks on each replica's step RPC in turn, so a cluster
    step costs ~N·d on top of the compute; (b) CONCURRENT — every step
    RPC issues before any harvests, so the N delays overlap and the
    step costs ~d. ASSERTED: outputs bitwise identical across arms
    (the determinism contract — completion order never changes cluster
    behavior), speedup (serial cluster_step_ms p50 / concurrent p50)
    >= 2.5x at N=3, step RPCs genuinely overlapped
    (rpc_inflight_peak >= replicas), zero rpc errors, zero
    steady-state recompiles per replica (strict retrace sanitizer),
    zero page leaks. Reported: per-arm cluster_step_ms p50/p99, the
    injected delay, per-RPC RTT p50/p99 and the in-flight depth peak.

    The injected delay is calibrated from the warmup's own measured
    step time (d = max(60ms, 6× compute) — large enough that the
    serial arm's N·d separates cleanly from the concurrent arm's d,
    small enough to keep the phase inside its budget), so the phase is
    meaningful on CPU and TPU alike: the speedup measures the drive
    loop's round-trip structure, which is platform-independent."""
    import time as _time

    import jax

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import ClusterManager, ServingConfig

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_replicas = 3
    n_new = 16 if on_tpu else 8
    prompt_len = 48 if on_tpu else 20
    if not on_tpu and kernels == "pallas":
        _log("serve_cluster_async: forcing kernels=xla off-TPU")
        kernels = "xla"

    prompts = [
        [(i * 53 + j * 17 + 11) % cfg.vocab_size
         for j in range(prompt_len)]
        for i in range(2 * n_replicas)
    ]

    def build(concurrent):
        sc = ServingConfig(
            max_requests_per_batch=4,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=16 if on_tpu else 8,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=64 if on_tpu else 8,
            replicas=n_replicas,
            router_policy="round_robin",
            replica_transport="loopback",
            concurrent_stepping=concurrent,
            sanitizers=("retrace",),
        )
        return ClusterManager.build(llama, cfg, params, sc)

    def run(concurrent, delay):
        cm = build(concurrent)
        # warm: compiles + the sanitizer's steady-state baseline, and
        # (first arm only) the compute-time estimate the injected
        # delay is calibrated from
        cm.generate(prompts, max_new_tokens=n_new)
        warm_step_ms = cm.stats.cluster_step_ms_p50
        if delay is None:
            delay = max(0.06, 6.0 * warm_step_ms / 1000.0)
        # measured window starts clean: drop the warmup's samples and
        # switch every link to the threaded worker with the real delay
        cm.stats.cluster_step_ms_samples.clear()
        for samples in cm.stats.rpc_rtt_ms_samples.values():
            samples.clear()
        for rep in cm.replicas:
            rep.transport.threaded = True
            rep.transport.delay_s = delay
        t0 = _time.perf_counter()
        outs = [
            list(r.output_tokens)
            for r in cm.generate(prompts, max_new_tokens=n_new)
        ]
        wall = _time.perf_counter() - t0
        st = cm.cluster_stats()
        for pos, rep in enumerate(cm.replicas):
            assert rep.rm.stats.retraces == 0, (
                f"replica {pos}: {rep.rm.stats.retraces} steady-state "
                "recompiles under the delayed link"
            )
        cm.check_no_leaks()
        for rep in cm.replicas:
            rep.close()
        return {
            "outs": outs,
            "delay": delay,
            "step_ms_p50": st["cluster_step_ms_p50"],
            "step_ms_p99": st["cluster_step_ms_p99"],
            "wall": wall,
            "stats": st,
        }

    serial = run(concurrent=False, delay=None)
    conc = run(concurrent=True, delay=serial["delay"])

    assert conc["outs"] == serial["outs"], (
        "concurrent stepping changed greedy outputs — the completion-"
        "order determinism contract is broken"
    )
    cs = conc["stats"]
    assert cs["rpc_errors"] == 0 and serial["stats"]["rpc_errors"] == 0
    assert cs["rpc_inflight_peak"] >= n_replicas, (
        f"step RPCs never overlapped (peak {cs['rpc_inflight_peak']})"
    )
    speedup = serial["step_ms_p50"] / conc["step_ms_p50"]
    assert speedup >= 2.5, (
        f"concurrent stepping {speedup:.2f}x vs serial at "
        f"N={n_replicas}, injected delay "
        f"{serial['delay'] * 1000:.0f}ms — the fan-out should "
        "approach one round-trip per step (>=2.5x)"
    )
    emit(
        "cluster_async_step_speedup",
        round(speedup, 3),
        "x",
        vs_baseline=round(speedup, 3),
        kernels=kernels,
        replicas=n_replicas,
        injected_rpc_delay_ms=round(serial["delay"] * 1000.0, 1),
        serial_cluster_step_ms_p50=round(serial["step_ms_p50"], 3),
        serial_cluster_step_ms_p99=round(serial["step_ms_p99"], 3),
        concurrent_cluster_step_ms_p50=round(conc["step_ms_p50"], 3),
        concurrent_cluster_step_ms_p99=round(conc["step_ms_p99"], 3),
        rpc_rtt_ms_p50=round(cs["rpc_rtt_ms_p50"], 3),
        rpc_rtt_ms_p99=round(cs["rpc_rtt_ms_p99"], 3),
        rpc_inflight_peak=cs["rpc_inflight_peak"],
        serial_wall_s=round(serial["wall"], 2),
        concurrent_wall_s=round(conc["wall"], 2),
        output_parity=1,
        errors=0,
        steady_state_recompiles=0,
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return speedup


def serve_fused_bench(on_tpu, kernels):
    """Megakernel decode step (serve/kernels.py fused prologue +
    serve/sampling.py fused epilogue, ``ServingConfig.fused_decode``):
    small-batch greedy decode on the blocking sync scheduler — the
    regime where per-step dispatch overhead and HBM round-trips
    dominate — ablating each fusion independently:

      base          fused_decode=()                  step + host decode head
      rope_kv_write in-kernel RoPE + KV page write   (Pallas path only)
      sampling      on-device mode-specialized head  ONE program per step
      both          the full megakernel step

    Reports decode_step_ms p50/p99, tokens/sec and DISPATCHED PROGRAMS
    per decode step (engine.dispatch_count) for every ablation, asserts
    BITWISE output parity of each fusion vs the unfused baseline,
    zero steady-state recompiles, and that the fused step issues
    strictly fewer programs per decode step than the unfused baseline.

    Measurement caveat (CPU): kernels is forced to "xla" off-TPU
    (interpret-mode Pallas would dominate), where "rope_kv_write" is by
    design a no-op — its row measures parity at ~1.0x, and only the
    chip measures the prologue's HBM/dispatch win. The "sampling"
    epilogue is an XLA-level fusion, so its halved per-step dispatch
    count (2 -> 1) and skipped (R, V) sorts are real on every backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import InferenceEngine, RequestManager, ServingConfig
    from flexflow_tpu.serve.request_manager import RequestStatus

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 8          # small-batch decode: the latency-bound regime
    n_new = 64 if on_tpu else 24
    prompt_len = 32 if on_tpu else 12
    page_size = 16
    if not on_tpu and kernels == "pallas":
        _log("serve_fused: forcing kernels=xla off-TPU (interpret-mode "
             "pallas would dominate the measurement)")
        kernels = "xla"

    prompts = [
        [(i * 37 + j * 11 + 3) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_slots)
    ]

    def make_rm(fused):
        sc = ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=prompt_len,
            max_spec_tree_tokens=8,
            cache_dtype=cfg.dtype,
            kernels=kernels,
            kv_layout="paged",
            page_size=page_size,
            # ample pool: preemption/reclaim dispatches would pollute
            # the per-step dispatch count under measurement
            max_cached_tokens=n_slots * (prompt_len + n_new + page_size),
            fused_decode=fused,
            sanitizers=("retrace",),
        )
        return RequestManager(InferenceEngine(llama, cfg, params, sc))

    def run(fused):
        rm = make_rm(fused)
        # the blocking sync scheduler: one host round-trip per step —
        # exactly the per-step dispatch overhead the megakernel attacks
        # (the pipelined path hides it behind dispatch-ahead instead)
        rm.supports_fast_decode = False
        rm.generate(prompts, max_new_tokens=2)   # warm every step key
        rm.stats = type(rm.stats)()
        eng = rm.engine
        rids = [rm.submit(p, max_new_tokens=n_new) for p in prompts]
        step_ms, decode_dispatches, n_decode = [], 0, 0
        t0 = time.perf_counter()
        while True:
            decode_only = (
                rm._active(RequestStatus.DECODING)
                and not rm._active(RequestStatus.PREFILLING)
            )
            d0 = eng.dispatch_count
            ts = time.perf_counter()
            if not rm.step():
                break
            if decode_only:
                step_ms.append((time.perf_counter() - ts) * 1e3)
                decode_dispatches += eng.dispatch_count - d0
                n_decode += 1
        rm.drain()
        wall = time.perf_counter() - t0
        outs = [list(rm.requests[r].output_tokens) for r in rids]
        tokens = sum(len(o) for o in outs)
        stats = rm.stats.snapshot()
        return {
            "fused": fused,
            "outputs": outs,
            "tps": tokens / wall,
            "p50_ms": float(np.percentile(step_ms, 50)),
            "p99_ms": float(np.percentile(step_ms, 99)),
            "dispatches_per_step": decode_dispatches / max(1, n_decode),
            "decode_steps": n_decode,
            "retraces": stats["retraces"],
        }

    ablations = {
        "base": (),
        "rope_kv_write": ("rope_kv_write",),
        "sampling": ("sampling",),
        "both": ("rope_kv_write", "sampling"),
    }
    res = {name: run(fused) for name, fused in ablations.items()}

    base = res["base"]
    for name, r in res.items():
        assert r["outputs"] == base["outputs"], (
            f"fused_decode={r['fused']} generations diverged from the "
            "unfused step — every fusion must be bitwise-identical"
        )
        assert r["retraces"] == 0, (
            f"fused_decode={r['fused']}: {r['retraces']} steady-state "
            "recompiles in the measured run"
        )
    assert res["both"]["dispatches_per_step"] < base["dispatches_per_step"], (
        "fused step must issue strictly fewer programs per decode step: "
        f"both={res['both']['dispatches_per_step']:.2f} vs "
        f"base={base['dispatches_per_step']:.2f}"
    )

    detail = {}
    for name, r in res.items():
        detail[f"{name}_decode_step_ms_p50"] = round(r["p50_ms"], 3)
        detail[f"{name}_decode_step_ms_p99"] = round(r["p99_ms"], 3)
        detail[f"{name}_tokens_per_sec"] = round(r["tps"], 2)
        detail[f"{name}_dispatches_per_step"] = round(
            r["dispatches_per_step"], 2
        )
    emit(
        "fused_decode_dispatches_per_step",
        round(res["both"]["dispatches_per_step"], 2),
        "programs/step",
        # <1: the fused step's per-decode-step program count vs unfused
        vs_baseline=(
            res["both"]["dispatches_per_step"]
            / max(1e-9, base["dispatches_per_step"])
        ),
        baseline_dispatches_per_step=round(base["dispatches_per_step"], 2),
        kernels=kernels,
        platform=_platform(),
    )
    emit(
        "fused_decode_step_ms_p50",
        round(res["both"]["p50_ms"], 3),
        "ms",
        # <1: fused decode-step latency vs the unfused baseline
        vs_baseline=res["both"]["p50_ms"] / max(1e-9, base["p50_ms"]),
        kernels=kernels,
        n_slots=n_slots,
        new_tokens_per_request=n_new,
        prompt_len=prompt_len,
        decode_steps_measured=res["both"]["decode_steps"],
        output_parity="bitwise",
        steady_state_recompiles=0,
        **detail,
        platform=_platform(),
    )
    return res["both"]["p50_ms"]


def serve_megakernel_bench(on_tpu, kernels):
    """Whole-step decode megakernel (fused_decode=("whole_step",),
    serve/kernels.whole_step_decode): small-batch greedy decode on the
    blocking sync scheduler, ablating

      base        fused_decode=()                   step + host decode head
      pr6         ("rope_kv_write", "sampling")     the PR-6 per-layer fusions
      whole_step  ("whole_step",)                   ONE layer-walking program
      whole_step_sub  whole_step under a squeezed FF_WHOLE_STEP_VMEM_MB
                    budget: the engine must pick a SUB-BLOCK tile count
                    (weight column streaming) instead of falling back
      whole_step+q  whole_step × quantized_allreduce="int8" on a TP2 mesh
                    (EQuARX collectives; skipped below 2 devices)

    Reports decode_step_ms p50/p99 (now sourced from SchedulerStats —
    the scheduler's own reservoir, derived decode_step_ms_p50 summary),
    dispatched programs per decode AND mixed step, and the
    program_launch_count structural launch proxy for both step shapes.
    Asserts BITWISE output parity of base / pr6 / whole_step /
    whole_step_sub, greedy parity of the quantized-collective arm vs
    its exact twin, zero steady-state recompiles everywhere, whole_step
    at ONE dispatched program per decode step, the sub-block arm at
    tiles>1 with whole_step_fallbacks == 0, ONE dispatched program per
    mixed step, and STRICTLY fewer kernel launches than the PR-6 fused
    decode step / the unfused mixed step.

    Measurement caveat (CPU): the whole-step walk runs interpret-mode
    Pallas off-TPU, so its decode_step_ms is an interpreter artifact —
    the CPU rows measure PARITY, dispatch counts and launch structure;
    only the chip measures the VMEM-streaming win (same caveat as
    serve_fused's rope_kv_write row). pr6/base run kernels=xla off-TPU
    for the same reason."""
    import functools
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.core.mesh import MachineSpec
    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import (
        InferenceEngine, RequestManager, ServingConfig,
    )
    from flexflow_tpu.serve.engine import program_launch_count
    from flexflow_tpu.serve.request_manager import RequestStatus

    cfg = _llm_cfg(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 4
    n_new = 32 if on_tpu else 10
    prompt_len = 32 if on_tpu else 8
    page_size = 16
    base_kernels = kernels if on_tpu else "xla"
    if not on_tpu and kernels == "pallas":
        _log("serve_megakernel: pr6/base arms run kernels=xla off-TPU "
             "(interpret-mode pallas would dominate); the whole_step "
             "arm necessarily runs its interpret-mode walk")

    prompts = [
        [(i * 37 + j * 11 + 3) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_slots)
    ]

    def make_rm(fused, mesh=None, collective=None, kern=None):
        sc = ServingConfig(
            max_requests_per_batch=n_slots,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=prompt_len,
            max_spec_tree_tokens=8,
            cache_dtype=cfg.dtype,
            kernels=kern or base_kernels,
            kv_layout="paged",
            page_size=page_size,
            max_cached_tokens=n_slots * (prompt_len + n_new + page_size),
            fused_decode=fused,
            quantized_allreduce=collective,
            sanitizers=("retrace",),
        )
        eng = InferenceEngine(llama, cfg, params, sc, mesh=mesh)
        return RequestManager(eng)

    def run(fused, mesh=None, collective=None, kern=None, env_mb=None):
        # env_mb: FF_WHOLE_STEP_VMEM_MB override scoped to ENGINE
        # CONSTRUCTION (the VMEM gate prices once, at __init__) — the
        # sub-block ablation squeezes the budget to force tiles>1
        old = os.environ.get("FF_WHOLE_STEP_VMEM_MB")
        if env_mb is not None:
            os.environ["FF_WHOLE_STEP_VMEM_MB"] = repr(env_mb)
        try:
            rm = make_rm(fused, mesh, collective, kern)
        finally:
            if env_mb is not None:
                if old is None:
                    os.environ.pop("FF_WHOLE_STEP_VMEM_MB", None)
                else:
                    os.environ["FF_WHOLE_STEP_VMEM_MB"] = old
        rm.supports_fast_decode = False  # sync: true per-step wall time
        rm.generate(prompts, max_new_tokens=2)   # warm every step key
        rm.stats = type(rm.stats)()
        eng = rm.engine
        rids = [rm.submit(p, max_new_tokens=n_new) for p in prompts]
        decode_dispatches, n_decode = 0, 0
        mixed_dispatches, n_mixed = 0, 0
        t0 = time.perf_counter()
        while True:
            decode_only = (
                rm._active(RequestStatus.DECODING)
                and not rm._active(RequestStatus.PREFILLING)
                and not rm.pending
            )
            # admission happens INSIDE step(): a step with queued or
            # half-prefilled requests is a prefill/mixed step
            mixed = bool(rm.pending
                         or rm._active(RequestStatus.PREFILLING))
            d0 = eng.dispatch_count
            if not rm.step():
                break
            if decode_only:
                decode_dispatches += eng.dispatch_count - d0
                n_decode += 1
            elif mixed:
                mixed_dispatches += eng.dispatch_count - d0
                n_mixed += 1
        rm.drain()
        wall = time.perf_counter() - t0
        outs = [list(rm.requests[r].output_tokens) for r in rids]
        stats = rm.stats.snapshot()
        return {
            "fused": fused,
            "outputs": outs,
            "tps": sum(len(o) for o in outs) / wall,
            # SchedulerStats' OWN reservoir — the new decode_step_ms
            # telemetry, not a bench-side stopwatch
            "p50_ms": stats["decode_step_ms_p50"],
            "p99_ms": stats["decode_step_ms_p99"],
            "dispatches_per_step": decode_dispatches / max(1, n_decode),
            "decode_steps": n_decode,
            "mixed_dispatches_per_step": mixed_dispatches / max(1, n_mixed),
            "mixed_steps": n_mixed,
            "retraces": stats["retraces"],
            "whole_step_on": getattr(eng, "whole_step_on", False),
            "whole_step_mixed_on": getattr(eng, "whole_step_mixed_on",
                                           False),
            "tiles": getattr(eng, "whole_step_tiles", 1),
            "mixed_tiles": getattr(eng, "whole_step_mixed_tiles", 1),
            "fallbacks": getattr(eng, "whole_step_fallbacks", 0),
            "vmem_est": getattr(eng, "whole_step_vmem_est", 0),
        }

    res = {
        "base": run(()),
        "pr6": run(("rope_kv_write", "sampling"),
                   kern=kernels if on_tpu else "xla"),
        "whole_step": run(("whole_step",)),
    }
    assert res["whole_step"]["whole_step_on"], (
        "whole_step fell back — VMEM pricing tripped on the bench shape"
    )

    # sub-block ablation: price the walk exactly the way the engine's
    # VMEM gate does, then squeeze FF_WHOLE_STEP_VMEM_MB between the
    # untiled working set and the first sub-block tiling so the engine
    # MUST stream weight column sub-tiles (tiles>1) — not fall back
    from flexflow_tpu.serve import kernels as _pk
    probe = make_rm(()).engine
    layer_arrays, head_arrays = llama.whole_step_weight_layout(
        params, cfg
    )
    roles = llama.whole_step_tile_roles(cfg)
    S_virt = probe.serving.pages_per_slot * probe.serving.page_size
    Cm = probe.serving.prefill_chunk

    def est(tiles, C):
        x0 = np.zeros((n_slots, C, cfg.hidden_size),
                      jnp.dtype(cfg.dtype))
        m = np.zeros((n_slots, C, S_virt), np.bool_)
        return _pk.whole_step_vmem_bytes(
            layer_arrays, head_arrays, probe.cache, x0, m,
            cfg.num_attention_heads, tiles=tiles, tile_roles=roles,
        )

    force = next(
        t for t in _pk.whole_step_tile_candidates(layer_arrays, roles)
        if t > 1
    )
    lo = max(est(force, 1), est(force, Cm))   # tiles=force must fit...
    hi = est(1, 1)                            # ...untiled decode must not
    assert lo < hi, (
        f"bench shape can't isolate sub-block streaming: tiles={force} "
        f"floor {lo} >= untiled working set {hi}"
    )
    del probe
    res["whole_step_sub"] = run(
        ("whole_step",), env_mb=(lo + hi) / 2 / (1024 * 1024)
    )
    sub = res["whole_step_sub"]
    assert sub["whole_step_on"] and sub["fallbacks"] == 0, (
        "sub-block ablation fell back — the squeezed budget must yield "
        f"a tile count, not a fallback (fallbacks={sub['fallbacks']})"
    )
    assert sub["tiles"] > 1, (
        "sub-block ablation picked tiles=1 — the squeezed budget "
        "failed to force weight sub-block streaming"
    )
    assert sub["whole_step_mixed_on"] and sub["mixed_tiles"] > 1, (
        "sub-block ablation must run the WHOLE-STEP MIXED walk with "
        f"sub-block streaming (mixed_on={sub['whole_step_mixed_on']}, "
        f"mixed_tiles={sub['mixed_tiles']})"
    )
    tp_ok = len(jax.devices()) >= 2
    if tp_ok:
        mesh = MachineSpec(model=2).make_mesh(jax.devices()[:2])
        res["whole_step_tp_exact"] = run(
            ("whole_step",), mesh=mesh, collective="exact"
        )
        res["whole_step_tp_q"] = run(
            ("whole_step",), mesh=mesh, collective="int8"
        )
    else:
        _log("serve_megakernel: <2 devices — skipping the TP2 "
             "quantized-allreduce ablation")

    base = res["base"]
    for name in ("base", "pr6", "whole_step", "whole_step_sub"):
        r = res[name]
        assert r["outputs"] == base["outputs"], (
            f"{name} generations diverged — whole-step decode must be "
            "bitwise the unfused step"
        )
    for name, r in res.items():
        assert r["retraces"] == 0, (
            f"{name}: {r['retraces']} steady-state recompiles"
        )
    for name in ("whole_step", "whole_step_sub"):
        assert res[name]["dispatches_per_step"] == 1.0, (
            f"{name} decode must stay ONE dispatched program: "
            f"{res[name]['dispatches_per_step']:.2f}"
        )
        assert res[name]["mixed_dispatches_per_step"] == 1.0, (
            f"{name} mixed steps must be ONE dispatched program "
            "(the whole-step mixed walk): "
            f"{res[name]['mixed_dispatches_per_step']:.2f} over "
            f"{res[name]['mixed_steps']} steps"
        )
    assert (res["whole_step"]["dispatches_per_step"]
            <= res["pr6"]["dispatches_per_step"] + 1e-9)
    assert (res["whole_step"]["dispatches_per_step"]
            < base["dispatches_per_step"])
    if tp_ok:
        # the quantized collective must not move greedy decode tokens
        assert (res["whole_step_tp_q"]["outputs"]
                == res["whole_step_tp_exact"]["outputs"]), (
            "int8 allreduce moved greedy tokens vs exact mode"
        )

    # structural launch proxy: the walk vs the PR-6 per-layer step
    R, NP = n_slots, -(-(prompt_len + n_new + 8 + 8 + 1) // page_size)
    pool_pages = n_slots * NP
    cache = llama.init_paged_kv_cache(cfg, pool_pages, page_size)
    pt = jnp.zeros((R, NP), jnp.int32)
    toks = jnp.zeros((R, 1), jnp.int32)
    pos = jnp.zeros((R, 1), jnp.int32)
    lidx = jnp.zeros((R,), jnp.int32)
    cl = NP * page_size - 1
    n_whole = program_launch_count(
        functools.partial(llama.serve_step_whole, cfg=cfg, cache_len=cl),
        params, cache, toks, pos, lidx, pt,
    )
    n_pr6 = program_launch_count(
        functools.partial(llama.serve_step_paged, cfg=cfg, cache_len=cl,
                          kernels="pallas", fused_rope=True),
        params, cache, toks, pos, lidx, None, None, pt,
    )
    assert n_whole < n_pr6, (
        "whole-step must execute strictly fewer kernel launches than "
        f"the PR-6 fused step: {n_whole} vs {n_pr6}"
    )
    # the sub-block walk stays ONE program: the counter recurses into
    # the kernel body (the tiled walk's slicing adds INTERNAL eqns) but
    # the O(L)-vs-O(1) launch-site ordering vs the per-layer step holds
    n_whole_sub = program_launch_count(
        functools.partial(llama.serve_step_whole, cfg=cfg, cache_len=cl,
                          tiles=force),
        params, cache, toks, pos, lidx, pt,
    )
    assert n_whole_sub < n_pr6, (
        "the sub-block walk must keep strictly fewer launch sites than "
        f"the PR-6 per-layer fused step: {n_whole_sub} vs {n_pr6}"
    )
    # mixed step shape: the whole-step MIXED walk vs the unfused
    # per-layer mixed step at the scheduler's prefill chunk
    toks_m = jnp.zeros((R, Cm), jnp.int32)
    pos_m = jnp.broadcast_to(
        jnp.arange(Cm, dtype=jnp.int32)[None, :], (R, Cm)
    )
    n_whole_mixed = program_launch_count(
        functools.partial(llama.serve_step_whole, cfg=cfg, cache_len=cl),
        params, cache, toks_m, pos_m, lidx, pt,
    )
    n_unfused_mixed = program_launch_count(
        functools.partial(llama.serve_step_paged, cfg=cfg, cache_len=cl,
                          kernels="xla"),
        params, cache, toks_m, pos_m, lidx, None, None, pt,
    )
    assert n_whole_mixed < n_unfused_mixed, (
        "the whole-step mixed walk must execute strictly fewer kernel "
        f"launches than the unfused mixed step: {n_whole_mixed} vs "
        f"{n_unfused_mixed}"
    )

    detail = {}
    for name, r in res.items():
        detail[f"{name}_decode_step_ms_p50"] = round(r["p50_ms"], 3)
        detail[f"{name}_decode_step_ms_p99"] = round(r["p99_ms"], 3)
        detail[f"{name}_tokens_per_sec"] = round(r["tps"], 2)
        detail[f"{name}_dispatches_per_step"] = round(
            r["dispatches_per_step"], 2
        )
        detail[f"{name}_mixed_dispatches_per_step"] = round(
            r["mixed_dispatches_per_step"], 2
        )
    emit(
        "whole_step_launches_per_decode_step",
        n_whole,
        "launch sites/step",
        # <1: the walk's structural launch count vs the PR-6 fused step
        vs_baseline=n_whole / max(1, n_pr6),
        pr6_launches_per_step=n_pr6,
        subblock_launches_per_step=n_whole_sub,
        mixed_launches_per_step=n_whole_mixed,
        unfused_mixed_launches_per_step=n_unfused_mixed,
        kernels=base_kernels,
        platform=_platform(),
    )
    emit(
        "whole_step_decode_step_ms_p50",
        round(res["whole_step"]["p50_ms"], 3),
        "ms",
        # off-TPU this ratio is an interpreter artifact (see docstring);
        # parity/dispatch/launch assertions are the CPU substance
        vs_baseline=res["whole_step"]["p50_ms"] / max(1e-9,
                                                      base["p50_ms"]),
        output_parity="bitwise",
        steady_state_recompiles=0,
        dispatches_per_decode_step=1.0,
        quantized_allreduce_ablation=(
            "greedy-parity-vs-exact" if tp_ok else "skipped (<2 devices)"
        ),
        cpu_caveat=(
            None if on_tpu else
            "whole_step arm runs interpret-mode Pallas: decode_step_ms "
            "is an interpreter artifact off-chip"
        ),
        n_slots=n_slots,
        new_tokens_per_request=n_new,
        decode_steps_measured=res["whole_step"]["decode_steps"],
        # sub-block streaming ablation: the squeezed budget forced a
        # tile count (not a fallback), bitwise the unfused step
        subblock_tiles=sub["tiles"],
        subblock_mixed_tiles=sub["mixed_tiles"],
        subblock_whole_step_fallbacks=sub["fallbacks"],
        subblock_vmem_est_bytes=sub["vmem_est"],
        whole_step_vmem_est_bytes=res["whole_step"]["vmem_est"],
        **detail,
        platform=_platform(),
    )
    return res["whole_step"]["p50_ms"]


def serve_quantized_bench(on_tpu, kernels, bits):
    """Weight-only int8/int4 serving (reference --8bit/4bit-quantization,
    file_loader.cc:651,710 + decompress kernels): decode is
    bandwidth-bound on the params read, so int8 weights should ~2x
    tokens/sec/chip. Same workload as serve_bench (shared
    _serve_workload) so fp vs quantized is one variable."""
    import jax

    from flexflow_tpu.models import llama
    from flexflow_tpu.quantization import quantize_params

    cfg, prompts, n_new, n_req, make_sc = _serve_workload(on_tpu)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params, bits=bits)
    del params
    rm, kernels = _make_rm(llama, cfg, qparams, make_sc, prompts, kernels)
    t0 = time.perf_counter()
    outs = rm.generate(prompts, max_new_tokens=n_new)
    dt = time.perf_counter() - t0
    tokens = sum(len(o.output_tokens) for o in outs)
    tps = tokens / dt
    emit(
        f"incr_decode_tokens_per_sec_int{bits}",
        round(tps, 2),
        "tokens/sec/chip",
        vs_baseline=tps / A100_INCR_TOKS_PER_SEC,
        kernels=kernels,
        quantization=f"int{bits}",
        model_params_b=round(llama.num_params(cfg) / 1e9, 3),
        platform=_platform(),
    )
    return tps


def serve_7b_bench(on_tpu, kernels):
    """True LLaMA-7B-shape serving on one chip via int4 weights
    (~3.5 GB) — the BASELINE.json headline model
    (reference inference/models/llama.cc:23). Weights are materialized
    directly in quantized form (a dense 7B bf16 tree would not leave
    room to quantize on-chip). Emits incremental first, then SpecInfer
    with the layer-skip draft."""
    import jax

    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import (
        InferenceEngine, RequestManager, SpecConfig, SpecInferManager,
        ServingConfig,
    )

    cfg = _llm_cfg_7b()
    qparams = _random_quantized_params(cfg, bits=4)
    n_new, n_req, prompt_len = 48, 4, 64
    prompts = [
        [(i * 37 + j * 11 + 3) % cfg.vocab_size for j in range(prompt_len)]
        for i in range(n_req)
    ]

    def make_sc(kern):
        return ServingConfig(
            max_requests_per_batch=n_req,
            max_sequence_length=prompt_len + n_new + 8,
            prefill_chunk=32,
            max_spec_tree_tokens=16,
            cache_dtype=cfg.dtype,
            kernels=kern,
        )

    rm, kernels = _make_rm(llama, cfg, qparams, make_sc, prompts, kernels)
    t0 = time.perf_counter()
    outs = rm.generate(prompts, max_new_tokens=n_new)
    dt = time.perf_counter() - t0
    tokens = sum(len(o.output_tokens) for o in outs)
    incr_steps = sum(o.profile.llm_decoding_steps for o in outs)
    incr_tps = tokens / dt
    emit(
        "incr_decode_tokens_per_sec_7b_int4",
        round(incr_tps, 2),
        "tokens/sec/chip",
        vs_baseline=incr_tps / A100_INCR_TOKS_PER_SEC,
        kernels=kernels,
        quantization="int4",
        model="llama-7b-shape",
        platform=_platform(),
    )

    dcfg, dparams = _layer_skip_draft(cfg, qparams, 2)
    spec = SpecConfig(beam_width=2, beam_depth=3)
    mgr = SpecInferManager(
        rm.engine, InferenceEngine(llama, dcfg, dparams, make_sc(kernels)),
        spec,
    )
    mgr.generate(prompts, max_new_tokens=4)
    t0 = time.perf_counter()
    outs = mgr.generate(prompts, max_new_tokens=n_new)
    spec_dt = time.perf_counter() - t0
    spec_tokens = sum(len(o.output_tokens) for o in outs)
    spec_steps = sum(o.profile.llm_decoding_steps for o in outs)
    accepted = sum(o.profile.accepted_tokens for o in outs)
    speculated = sum(o.profile.speculated_tokens for o in outs)
    spec_tps = spec_tokens / spec_dt
    emit(
        "specinfer_tokens_per_sec_7b_int4",
        round(spec_tps, 2),
        "tokens/sec/chip",
        vs_baseline=spec_tps / A100_SPECINFER_TOKS_PER_SEC,
        kernels=kernels,
        quantization="int4",
        model="llama-7b-shape",
        spec_step_reduction=round(incr_steps / max(1, spec_steps), 3),
        drafted_accept_rate=round(accepted / max(1, speculated), 3),
        tokens_per_verify_step=round(spec_tokens / max(1, spec_steps), 3),
        incr_tokens_per_sec=round(incr_tps, 2),
        platform=_platform(),
    )
    return spec_tps


def _platform():
    import jax

    return jax.devices()[0].platform


# ----------------------------------------------------------------------
# child entry


def child_main(phase, platform, kernels):
    if phase == "serve_megakernel" and platform == "cpu":
        # the quantized-allreduce ablation needs a TP2 mesh: give the
        # CPU child two virtual devices BEFORE jax initialises
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
    import jax

    from flexflow_tpu.config import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != platform:
        sys.exit(f"[bench] child {phase}: asked for {platform!r}, JAX "
                 f"found {dev.platform!r}")
    on_tpu = dev.platform == "tpu"
    _log(f"child {phase}: backend {dev.platform}")
    if phase == "train":
        train_bench(on_tpu)
    elif phase == "searched":
        searched_train_bench(on_tpu)
    elif phase == "parity":
        kernel_parity(on_tpu)
    elif phase == "serve":
        serve_bench(on_tpu, kernels)
    elif phase == "serve_paged":
        serve_paged_bench(on_tpu, kernels)
    elif phase == "serve_continuous":
        serve_continuous_bench(on_tpu, kernels)
    elif phase == "serve_prefix":
        serve_prefix_bench(on_tpu, kernels)
    elif phase == "serve_paged_q":
        serve_paged_q_bench(on_tpu, kernels)
    elif phase == "serve_kv_hierarchy":
        serve_kv_hierarchy_bench(on_tpu, kernels)
    elif phase == "serve_long_context":
        serve_long_context_bench(on_tpu, kernels)
    elif phase == "serve_spec_adaptive":
        serve_spec_adaptive_bench(on_tpu, kernels)
    elif phase == "serve_spec_distill":
        serve_spec_distill_bench(on_tpu, kernels)
    elif phase == "serve_fused":
        serve_fused_bench(on_tpu, kernels)
    elif phase == "serve_megakernel":
        serve_megakernel_bench(on_tpu, kernels)
    elif phase == "serve_int8":
        serve_quantized_bench(on_tpu, kernels, bits=8)
    elif phase == "serve_int4":
        serve_quantized_bench(on_tpu, kernels, bits=4)
    elif phase == "serve_cluster":
        serve_cluster_bench(on_tpu, kernels)
    elif phase == "serve_faults":
        serve_faults_bench(on_tpu, kernels)
    elif phase == "serve_elastic":
        serve_elastic_bench(on_tpu, kernels)
    elif phase == "serve_transport":
        serve_transport_bench(on_tpu, kernels)
    elif phase == "serve_autotune":
        serve_autotune_bench(on_tpu, kernels)
    elif phase == "serve_cluster_async":
        serve_cluster_async_bench(on_tpu, kernels)
    elif phase == "serve_7b":
        serve_7b_bench(on_tpu, kernels)
    else:
        raise SystemExit(f"unknown phase {phase}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--metric",
        default="all",
        choices=["all", "train", "searched", "parity", "serve",
                 "serve_paged", "serve_continuous", "serve_prefix",
                 "serve_paged_q", "serve_kv_hierarchy",
                 "serve_long_context", "serve_cluster",
                 "serve_faults", "serve_elastic", "serve_transport",
                 "serve_cluster_async", "serve_autotune",
                 "serve_spec_adaptive", "serve_spec_distill", "serve_fused",
                 "serve_megakernel", "serve_int8", "serve_int4", "serve_7b"],
        help="run a single phase (default: all, insurance-first order)",
    )
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--platform", default="cpu", help=argparse.SUPPRESS)
    ap.add_argument("--kernels", default="xla", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        child_main(args.child, args.platform, args.kernels)
        return
    orchestrate(args.metric)


if __name__ == "__main__":
    main()

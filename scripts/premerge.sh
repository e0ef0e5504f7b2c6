#!/usr/bin/env bash
# premerge.sh — the one-command pre-merge gate.
#
# Runs, in order of increasing cost and on CPU (JAX_PLATFORMS=cpu, so
# it works on any dev box):
#   1. ffcheck            — static JAX/TPU hazard lint (zero findings)
#   2. family re-exports  — every model family exposes the serve API
#   3. fused parity       — the fast megakernel decode-step suite:
#                           fused-vs-unfused bitwise parity + the
#                           retrace-guard churn tests (zero steady-state
#                           recompiles with both fusions on)
#   4. KV hierarchy       — int4 packed pages + host spill tier:
#                           nibble-unpack parity, bitwise cold/warm/
#                           spilled-readmit parity, spill bookkeeping
#   5. cluster serving    — router placement/affinity/shed units,
#                           1-replica == bare-engine bitwise, and
#                           prefill→decode page migration byte-exact
#                           over fp/int8/int4 with zero page leaks
#   6. fault tolerance    — replica health/circuit-breaker units,
#                           deterministic fault injection, failover
#                           bitwise vs fault-free, seeded chaos with
#                           zero hangs/leaks, migration back-pressure
#   7. adaptive spec      — tree-shaping controller + spec==incremental
#                           bitwise parity across every composition
#   8. long context       — context-parallel serving: striped allocator
#                           invariants, CP-vs-single-shard bitwise
#                           parity, ring shard_map kernel parity,
#                           CP retrace churn
#   9. replica transport  — wire-codec byte-exactness, loopback
#                           cluster bitwise the in-process one (page
#                           migration included), transport fault
#                           chaos, heartbeat gaps, warm-standby
#                           adoption, subprocess replica server
#  10. observability       — cluster-wide tracing (stitched cross-
#                           replica timelines), Prometheus export with
#                           the counter drift guard, flight-recorder
#                           dumps matching health transitions,
#                           disabled-mode zero-overhead proof
#  11. elastic control plane — durable request journal (round-trip,
#                           torn-tail truncation, compaction),
#                           manager kill-restart recovery bitwise,
#                           scale_out warm joins / scale_in drains
#                           leak-free / set_pools under traffic,
#                           replica-death + manager-death chaos
#  12. concurrent stepping  — multiplexed async RPC transport:
#                           call-tag demux of out-of-order socket
#                           responses, the re-dial race (one
#                           reconnect), the duplicate-seq at-most-
#                           once race, concurrent-vs-serial drive
#                           loops bitwise under reordered completions
#                           (seeded chaos included), the pinned
#                           one-observation-per-step guard, in-flight
#                           depth / step+RTT percentile telemetry
#  13. self-driving serving — serving cost model structural sanities
#                           (capacity monotone in replicas, quantized-
#                           KV page multiplication), traffic-estimator
#                           bit-determinism on the step clock, offline
#                           search emitting validate_cluster-accepted
#                           configs, autoscaler hysteresis/cooldown/
#                           dry-run units, journaled burst scale_out→
#                           scale_in e2e bitwise vs static, mid-scale-
#                           event SIGKILL recovery
#  14. concurrency analysis  — the ffcheck concurrency rules
#                           (FF109 wall-clock-in-step-logic, FF110
#                           unguarded-shared-state, FF111
#                           held-lock-blocking-call) over their test
#                           fixtures, the wire-protocol drift check
#                           and lock-order cycle check, the runtime
#                           lock sanitizer units (injected inversion
#                           raises), and the sanitizer-on == -off
#                           bitwise suites (transport chaos, SIGKILL
#                           recovery, autoscaler drive loop)
#  15. distilled drafts +   — verify-skip state-machine units (skip at
#      verify-skip            cold (1,1), re-probe cadence, warm-up
#                           exit), skip arm == incremental bitwise
#                           with SSM cache debt repaid, distillation
#                           harvest/train determinism on the pinned-
#                           threefry CPU backend, checkpoint round-
#                           trip, accept-rate-per-draft-GFLOP ranking
#                           + measured-rate cost-model feed,
#                           skip/re-probe flapping compiling a
#                           bounded step-key set
#
# Exits non-zero at the first failing gate. Full tier-1 (ROADMAP.md
# "Tier-1 verify") is the merge bar; this is the fast inner loop.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

echo "== premerge 1/15: ffcheck (static hazard lint)" >&2
python scripts/ffcheck.py

echo "== premerge 2/15: family serve-API re-exports" >&2
python scripts/check_family_reexports.py

echo "== premerge 3/15: fused decode parity + retrace guard" >&2
# unfiltered: runs the interpret-mode Pallas e2e tests that tier-1
# slow-marks for time-budget reasons
python -m pytest tests/test_fused_decode.py tests/test_retrace_guard.py \
    -q -p no:cacheprovider

echo "== premerge 4/15: hierarchical KV cache (int4 + host spill)" >&2
# Pallas/XLA nibble-unpack parity, bitwise cold/warm/spilled-readmit
# generation parity over fp+int8+int4 pools, spill-tier bookkeeping
python -m pytest tests/test_kv_hierarchy.py -q -p no:cacheprovider

echo "== premerge 5/15: cluster serving (router + migration)" >&2
# router units, cluster-vs-bare-engine bitwise parity, disaggregated
# prefill→decode migration over fp/int8/int4, shed-is-terminal
python -m pytest tests/test_cluster.py -q -p no:cacheprovider

echo "== premerge 6/15: fault-tolerant cluster serving" >&2
# health state machine + circuit breaker, deterministic FaultPlan
# injection, replica-death failover bitwise vs the fault-free run,
# seeded chaos (every request terminal, zero leaks on survivors),
# migration queue back-pressure, pool-death fallbacks
python -m pytest tests/test_cluster_faults.py -q -p no:cacheprovider

echo "== premerge 7/15: adaptive speculation" >&2
# tree-shaping controller units, spec==incremental bitwise parity over
# fp/int8/int4 pools + prefix-cache hits + continuous-batching churn,
# early-exit self-draft, cluster SSM-mirror smoke
python -m pytest tests/test_adaptive_spec.py -q -p no:cacheprovider

echo "== premerge 8/15: context-parallel long-context serving" >&2
# striped allocator invariants, CP-vs-single-shard bitwise parity
# (fp/int8; int4 at tolerance), chunked prefill across shards, spill/
# readmit + preemption under CP, ring shard_map kernel parity on a
# seq=2 mesh, CP retrace churn (one program per step key)
python -m pytest tests/test_long_context.py -q -p no:cacheprovider

echo "== premerge 9/15: replica RPC transport + warm standbys" >&2
# unfiltered: runs the int8/int4 loopback parity params and the
# subprocess replica-server tests that tier-1 slow-marks — wire-codec
# byte-exactness, loopback cluster bitwise the in-process PR-8/9
# cluster (disaggregated page migration over the wire included),
# transport fault chaos (drop/delay/disconnect/partition), heartbeat
# gaps + the one-observation-per-step guard, warm-standby adoption
python -m pytest tests/test_transport.py -q -p no:cacheprovider

echo "== premerge 10/15: observability (tracing + export + recorder)" >&2
# unfiltered: runs the subprocess-replica envelope-shipping test and
# the trace-determinism re-run that tier-1 slow-marks — stitched
# fault-injected loopback timeline (one trace id across both replicas
# + the wire hop), Prometheus snapshot through the exporter drift
# guard (every SchedulerStats/ClusterStats/ProfileInfo field exported
# or explicitly excluded), flight-recorder dump matching the health
# machine's recorded transition, FF108 tracer-sync rule, and the
# disabled-mode proof (no tracer calls, no obs allocations, identical
# dispatched-programs-per-step)
python -m pytest tests/test_observability.py -q -p no:cacheprovider

echo "== premerge 11/15: elastic control plane (journal + reconfigure)" >&2
# unfiltered: runs the int8 kill-restart, subprocess reconnect and
# sigkill-chaos tests that tier-1 slow-marks — journal round-trip +
# torn-tail truncation + compaction, manager kill-restart bitwise the
# uninterrupted run (stream-monotone across the restart), scale_out
# warm-vs-cold, scale_in drains with zero leaks/held slots, set_pools
# under traffic bitwise vs static membership, seeded replica+manager
# death chaos
python -m pytest tests/test_elastic.py -q -p no:cacheprovider

echo "== premerge 12/15: concurrent cluster stepping (async transport)" >&2
# unfiltered: runs the subprocess two-server fan-out test that tier-1
# slow-marks — RpcFuture deadline/issue semantics, socket call-tag
# demux of out-of-order responses, the serialized re-dial race, the
# duplicate-seq at-most-once race (retry racing its own in-flight
# attempt executes once), the
# concurrent drive loop bitwise the serial loop under inverted-delay
# completion reordering + seeded fault chaos, the one-observation-per-
# step guard pinned under both loops, router prefix fan-out ordering,
# and the rpc_inflight_peak / cluster_step_ms / per-replica RTT
# telemetry through the Prometheus exporter
python -m pytest tests/test_transport_async.py -q -p no:cacheprovider

echo "== premerge 13/15: self-driving serving (autotune + autoscaler)" >&2
# unfiltered: runs the burst scale_out→scale_in e2e, the mid-scale-
# event SIGKILL recovery and the advise-mode e2e that tier-1 slow-
# marks — cost-model monotonicity/feasibility units, estimator
# determinism + pre-envelope gating, search fail-before-emit +
# spec×disagg pruning, policy hysteresis/cooldown/dead-band/dry-run
# over a scripted cost model, decision journaling (replay-inert),
# completion-window + per-replica arrival/completion reconciliation
python -m pytest tests/test_autotune.py -q -p no:cacheprovider

echo "== premerge 14/15: concurrency analysis + lock sanitizer" >&2
# the three PR-19 AST rules + drift/lock-order whole-program checks
# over their fixture corpus (must lint clean — the fixtures exercise
# the suppression/registry syntax premerge depends on), the sanitizer
# unit suite (injected lock-order inversion fails loudly), and the
# slow-marked sanitizer-on == sanitizer-off bitwise variants of the
# transport-chaos / SIGKILL-recovery / autoscaler-drive suites
python scripts/ffcheck.py tests/fixtures/ffcheck
python -m pytest tests/test_locks.py tests/test_ffcheck.py \
    -q -p no:cacheprovider
python -m pytest tests/test_transport.py tests/test_elastic.py \
    tests/test_autotune.py -q -p no:cacheprovider \
    -k "locks_sanitizer"

echo "== premerge 15/15: distilled drafts + verify-skip" >&2
# unfiltered: runs the verify-skip flapping churn variant that tier-1
# slow-marks — verify-skip
# controller units + skip-arm bitwise parity (SSM lag repaid),
# distillation determinism / checkpoint round-trip / draft ranking,
# measured accept rate overriding the cost model's workload prior
python -m pytest tests/test_spec_distill.py -q -p no:cacheprovider
python -m pytest tests/test_retrace_guard.py -q -p no:cacheprovider \
    -k "verify_skip_flapping"

echo "premerge: all gates passed" >&2

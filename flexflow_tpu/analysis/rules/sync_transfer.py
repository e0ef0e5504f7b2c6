"""FF107 sync-transfer: blocking device↔host transfers on the serving
hot path.

The hierarchical KV cache spills cold prefix pages to host RAM and
re-admits them on a hit (serve/prefix_cache.py). That tier is only free
because every transfer is ASYNC: ``fetch_page`` starts a
``copy_to_host_async`` and the handle is harvested at the scheduler's
flush (already a sync point); ``upload_page`` relies on dispatch
ordering. A stray ``jax.device_get`` (or blocking ``jax.device_put`` /
``block_until_ready``) introduced anywhere the scheduler's dispatch
path can reach would serialize the dispatch-ahead pipeline — every
decode step would wait out a PCIe round-trip, the exact stall the
spill tier is designed never to cause.

Unlike FF101 (host syncs inside jit-TRACED code), this rule walks the
HOST-side scheduler: functions in ``flexflow_tpu/serve/`` reachable —
through the file-local call graph, ``self.``-method calls included —
from the serving hot-path roots (``step``/dispatch/admission/page
reservation/prefix-cache attach+reclaim and the engine's ``run*``
dispatch methods). Paths that block BY DESIGN (the pipeline flush, the
blocking sync scheduler, triage dumps) carry explicit suppressions
with reasons — the point is that every blocking transfer on the hot
path is a reviewed decision, not an accident.

Suppress with ``# ffcheck: disable=FF107 -- reason``.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Set

from ..lint import FileContext, Finding, FuncDef, Rule

# Host-side entry points of the serving hot path: the scheduler's step
# loop and everything it runs per iteration, the admission/page-
# reservation path (where spill/readmit live), and the engine's
# dispatch methods. Reachability is computed from these by name over
# the file-local call graph.
HOT_ROOTS = {
    "step",
    "_step_pipelined",
    "_dispatch_mixed",
    "_dispatch_decode",
    "_reserve_active_pages",
    "_admit_pending",
    "_reclaim_slots_for_admission",
    "_trim_pipeline",
    "attach",
    "reclaim",
    "run",
    "run_mixed",
    "run_decode",
    "run_sampled",
    "run_speculate",
    "commit",
    "reorder",
    "copy_page",
    "fetch_page",
    "upload_page",
    # cluster serving (serve/cluster/): the router/manager drive loop
    # and the prefill→decode migration — its one blocking harvest is a
    # designed flush point and must carry a reasoned suppression
    "submit",
    "route",
    "migrate_request",
    "_queue_migrations",
    "_drain_migration_queue",
    "_recompute_readmit",
    # fault tolerance (health/failover/probe): everything that runs
    # when a replica dies or recovers is ON the drive loop — a blocking
    # transfer in a failover would stall every healthy replica's decode
    # exactly when the cluster is degraded
    "_place",
    "_on_replica_down",
    "_run_failovers",
    "_schedule_failover",
    "abandon",
    "on_step",
    "record_failure",
    "record_success",
    "maybe_probe",
    # context-parallel long-context serving (kv_shard="context"): the
    # per-shard admission/allocation path (striped ensure/COW/readmit
    # run inside admissions and page reservation) and the ring ragged
    # paged attention entry points — a blocking transfer anywhere here
    # would stall every decode step on a 100k-token request's critical
    # path
    "ensure",
    "take_free_page",
    "cow",
    "splice",
    "release",
    "_readmit",
    "_admission_error",
    "_ensure_pages",
    "shard_balance",
    "ring_ragged_paged_attention",
    "ring_ragged_paged_attention_xla",
    # replica RPC transport (serve/cluster/transport.py + remote.py +
    # server.py): the RPC send/recv core, heartbeats and the server's
    # dispatch table all run ON the cluster drive loop — a blocking
    # device transfer anywhere here would stall every replica's decode
    # behind one replica's PCIe round-trip. The two reviewed flush
    # points (the wire migration harvest in _m_migrate_out and the
    # standby tree-export harvest in export_tree) carry reasoned
    # suppressions; the server's handlers are reached dynamically
    # (getattr dispatch), so each one is its own root.
    "call",
    "_rpc",
    "heartbeat",
    "_heartbeat_remote",
    "_check_gap",
    "_observe_failure",
    # concurrent cluster stepping (multiplexed transport + fan-out
    # drive loop): the async issue/harvest pair, the per-connection
    # reader/worker loops that complete futures off-thread, and the
    # manager's concurrent step — ALL of it is the cluster's
    # once-per-step critical path, and a blocking device transfer in
    # an issue phase serializes the very RPCs the fan-out exists to
    # overlap
    "call_async",
    "step_async",
    "finish_step",
    "heartbeat_async",
    "finish_heartbeat",
    "prefix_score_async",
    "finish_prefix_score",
    "prefix_score",
    "wait",
    "result",
    "_prefix_scores",
    "_step_replicas_serial",
    "_step_replicas_concurrent",
    "_apply_step_failure",
    "_reader_loop",
    "_worker_loop",
    "_fail_pending",
    "dispatch",
    "_m_step",
    "_m_heartbeat",
    "_m_submit",
    "_m_migrate_out",
    "_m_migrate_in",
    "_m_export_tree",
    "_m_import_tree",
    "migrate_out",
    "migrate_in",
    "_migrate_remote",
    "export_tree",
    "import_tree",
    "_adopt_standby",
    # elastic control plane (serve/cluster/journal.py + reconfigure.py):
    # the journal's append/flush run at the drive loop's flush sync
    # point EVERY cluster step and the reconfiguration ops run under
    # live traffic — a blocking device transfer (or a hot-path fsync
    # smuggled in as one) anywhere here would stall every replica's
    # decode behind control-plane bookkeeping. The retire-time tree
    # hand-off reuses export_tree's reviewed harvest suppression.
    "append",
    "append_now",
    "flush",
    "_journal_sync",
    "_journal_checkpoint",
    "compact",
    "scale_out",
    "begin_scale_in",
    "maybe_retire",
    "_retire",
    "_warm_join",
    "set_pools",
    "rebuild_routing",
    "on_cluster_step",
    # self-driving serving (serve/autotune/): the autoscaler's per-step
    # hook, its evaluation + decision paths and the estimator's
    # observation fold all run INSIDE ClusterManager.step — host-side
    # counter arithmetic only, and a blocking device transfer smuggled
    # into any of them would tax every cluster step. on_step is already
    # a root (fault injection shares the name); these cover the rest of
    # the policy/estimator drive-loop surface. observe/observe_cluster/
    # profile fold the telemetry; predict prices a candidate; the
    # _decide_* and _sweep_completions paths mutate cluster state.
    "observe",
    "observe_cluster",
    "profile",
    "predict",
    "_evaluate",
    "_decide_scale_out",
    "_decide_scale_in",
    "_maybe_retune",
    "_sweep_completions",
    "drain_completion_window",
    "rate_snapshot",
    # draft distillation (serve/spec_distill.py): the harvest path
    # fetches teacher logits by design — an offline/side-channel tool,
    # but it lives in serve/ and attaches a sink the verify round
    # calls, so every blocking fetch it can reach must be a reviewed
    # suppression, not a silent sync the sink smuggles onto the hot
    # path. measure_draft_utility drives the live verify ladder; the
    # skip arm's incremental decode rides the existing ``step`` root.
    "harvest_online",
    "harvest_offline",
    "train_distilled_draft",
    "measure_draft_utility",
}

# Calls that force a synchronous transfer / device round-trip.
# ``.copy_to_host_async()`` is the blessed idiom and is not listed.
SYNC_PATHS = {
    "jax.device_get",
    "jax.device_put",
    "jax.block_until_ready",
}
SYNC_METHODS = {"block_until_ready"}


class SyncTransferRule(Rule):
    code = "FF107"
    slug = "sync-transfer"
    doc = (
        "synchronous device<->host transfer (jax.device_get / blocking "
        "jax.device_put / block_until_ready) reachable from the serving "
        "hot path — spill-tier traffic must stay async"
    )

    def _applies(self, ctx: FileContext) -> bool:
        path = ctx.path.replace("\\", "/")
        return "/serve/" in path or path.startswith("serve/")

    def _reachable(self, ctx: FileContext) -> Set[ast.AST]:
        """Functions reachable from HOT_ROOTS over the file-local call
        graph. Both plain-name calls (``attach(...)``) and method calls
        (``self._flush_one(...)``) resolve by the callee's simple name
        — the safe over-approximation for a one-file class."""
        by_name: Dict[str, List[ast.AST]] = {}
        for fn in ctx.functions:
            by_name.setdefault(fn.name, []).append(fn)
        reachable: Set[ast.AST] = {
            fn for fn in ctx.functions if fn.name in HOT_ROOTS
        }
        changed = True
        while changed:
            changed = False
            for fn in list(reachable):
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    name = None
                    if isinstance(node.func, ast.Name):
                        name = node.func.id
                    elif isinstance(node.func, ast.Attribute):
                        name = node.func.attr
                    for callee in by_name.get(name, ()):
                        if callee not in reachable:
                            reachable.add(callee)
                            changed = True
        # nested defs inherit their enclosing function's reachability
        for fn in ctx.functions:
            if fn in reachable:
                continue
            anc = ctx.enclosing_function(fn)
            while anc is not None:
                if anc in reachable:
                    reachable.add(fn)
                    break
                anc = ctx.enclosing_function(anc)
        return reachable

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not self._applies(ctx):
            return
        reachable = self._reachable(ctx)
        seen: Set[int] = set()
        for fn in reachable:
            for stmt in fn.body if isinstance(fn, FuncDef) else []:
                for node in ast.walk(stmt):
                    if (
                        not isinstance(node, ast.Call)
                        or id(node) in seen
                    ):
                        continue
                    seen.add(id(node))
                    path = ctx.resolve(node.func)
                    if path in SYNC_PATHS:
                        yield self.finding(
                            ctx, node,
                            f"{path} on the serving hot path blocks the "
                            "dispatch pipeline on a device round-trip — "
                            "use the async spill idiom "
                            "(copy_to_host_async + harvest at flush), "
                            "or suppress with a reason if this path "
                            "blocks by design",
                        )
                    elif (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr in SYNC_METHODS
                        and not node.args
                    ):
                        yield self.finding(
                            ctx, node,
                            f".{node.func.attr}() on the serving hot "
                            "path stalls until the device drains — the "
                            "hot loop must never wait on a transfer",
                        )


RULE = SyncTransferRule()

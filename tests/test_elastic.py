"""Elastic, crash-recoverable control plane (serve/cluster/journal.py +
reconfigure.py + ClusterManager.recover).

The contracts under test:

* **Journal** — CRC-framed records round-trip bitwise; a torn tail (a
  crash mid-write) recovers by TRUNCATION at the last whole record,
  never by corruption; compaction retires finished entries and a
  compacted log replays indistinguishably from the full history.
* **Manager restart recovery** — a killed-and-restarted ClusterManager
  replays the journal and re-admits every unfinished request through
  the recompute path with its journaled prompt + flushed prefix, so
  greedy outputs are BITWISE the uninterrupted run's, the pre-crash
  flushed (= streamed) tokens are a prefix of the recovered output
  (stream-monotone, zero duplicates), and no request is lost. The
  subprocess variant proves the manager reconnects to STILL-RUNNING
  replica servers.
* **Live reconfiguration** — scale_out enters routing WARM (donor
  prefix subtrees shipped before the first placement), scale_in fully
  drains (router places nothing on a DRAINING replica; the retiree
  passes check_no_leaks with zero held slots; its sessions re-pin and
  land warm on survivors), set_pools flips prefill/decode pools under
  traffic bitwise vs a static-membership run — every op journaled, so
  recovery rebuilds the post-reconfiguration membership.
* **Chaos** — replica death plus a scripted manager crash in one
  seeded run: every request reaches a terminal state, survivors are
  leak-free.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    ClusterManager,
    InferenceEngine,
    RequestManager,
    RequestStatus,
    ServingConfig,
)
from flexflow_tpu.serve.cluster import (
    Fault,
    FaultInjector,
    FaultPlan,
    InjectedManagerCrash,
    RequestJournal,
    replay_journal,
)
from flexflow_tpu.serve.cluster.faults import (
    PROCESS_KINDS,
    REPLICA_KINDS,
    TRANSPORT_KINDS,
)
from flexflow_tpu.serve.cluster.journal import encode_record, live_records
from flexflow_tpu.serve.request_manager import TERMINAL_STATUSES


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def sc_kwargs(**kw):
    base = dict(
        max_requests_per_batch=4,
        max_sequence_length=96,
        prefill_chunk=8,
        max_spec_tree_tokens=8,
        cache_dtype=jnp.float32,
        kv_layout="paged",
        page_size=16,
    )
    base.update(kw)
    return base


PROMPTS = [
    [3, 17, 91, 42, 7],
    [9, 8, 7, 6, 5, 4],
    [1, 2, 3, 4, 5, 6, 7, 8, 9],
    [11, 22, 33],
]


def _gen(gen=None):
    from flexflow_tpu.serve import GenerationConfig

    return gen or GenerationConfig()


def _cluster(tiny, **kw):
    cfg, params = tiny
    return ClusterManager.build(
        llama, cfg, params, ServingConfig(**sc_kwargs(**kw))
    )


def _outputs(cm, n_new=8, prompts=PROMPTS):
    return [
        list(r.output_tokens)
        for r in cm.generate(prompts, max_new_tokens=n_new)
    ]


_REFERENCE = {}


def _reference(tiny, **kw):
    """``_outputs`` of an undisturbed cluster at ``sc_kwargs(**kw)``,
    computed once a configuration: greedy decoding is deterministic (what
    the tests here assert bitwise), and every cluster built compiles its
    replicas' step programs again (ROADMAP A13)."""
    key = tuple(sorted((k, v) for k, v in kw.items() if v is not None))
    if key not in _REFERENCE:
        _REFERENCE[key] = _outputs(_cluster(tiny, **kw))
    return _REFERENCE[key]


def _finish(cm, cids, max_steps=4000):
    steps = 0
    while any(not cm._terminal(c) for c in cids):
        steps += 1
        assert steps < max_steps, (
            f"requests hung: {[c for c in cids if not cm._terminal(c)]}"
        )
        if not cm.step():
            cm.drain()
            if any(not cm._terminal(c) for c in cids):
                break
    cm.drain()
    return [list(cm.result(c).output_tokens) for c in cids]


def no_held_slots(cm):
    for rep in cm.replicas:
        assert rep.rm.hold_finished == set(), (
            f"replica {rep.index} still holds {rep.rm.hold_finished}"
        )


# ---------------------------------------------------------------------------
# journal units (no engine)


def test_journal_roundtrip(tmp_path):
    from flexflow_tpu.serve.cluster.server import gen_to_wire

    path = str(tmp_path / "j.journal")
    j = RequestJournal(path)
    j.append({"type": "submit", "cid": 1, "tokens": [5, 6, 7],
              "prompt_len": 3, "gen": gen_to_wire(_gen()),
              "session": "chat-1", "prompt": ""})
    j.append({"type": "tokens", "cid": 1, "toks": [10, 11]})
    j.flush()
    j.append_now({"type": "tokens", "cid": 1, "toks": [12]})
    j.append_now({"type": "terminal", "cid": 1, "error": None})
    j.append_now({
        "type": "members",
        "members": [{"index": 0, "role": "mixed", "endpoint": ""}],
    })
    j.close()

    state = replay_journal(path)
    assert state.records == 5 and state.truncated_bytes == 0
    e = state.entries[1]
    assert e.tokens == [5, 6, 7] and e.prompt_len == 3
    assert e.flushed == [10, 11, 12]
    assert e.terminal and e.error is None
    assert e.session == "chat-1"
    assert e.gen.max_new_tokens == _gen().max_new_tokens
    assert state.members == [
        {"index": 0, "role": "mixed", "endpoint": ""}
    ]
    assert state.next_cid == 2


def test_journal_torn_tail_truncates(tmp_path):
    """Every torn-tail shape — partial header, short payload, flipped
    payload byte — recovers by truncation to the last whole record,
    and the truncated file appends cleanly afterwards."""
    path = str(tmp_path / "j.journal")
    j = RequestJournal(path)
    for cid in (1, 2):
        j.append_now({"type": "tokens", "cid": cid, "toks": [cid]})
    j.close()
    good = os.path.getsize(path)

    frame = encode_record({"type": "tokens", "cid": 3, "toks": [3]})
    for torn in (frame[:5], frame[:-2],
                 frame[:-1] + bytes([frame[-1] ^ 0xFF])):
        with open(path, "r+b") as f:
            f.truncate(good)
            f.seek(good)
            f.write(torn)
        state = replay_journal(path)
        assert state.records == 2, f"torn tail {torn!r} leaked a record"
        assert state.truncated_bytes == len(torn)
        assert os.path.getsize(path) == good  # file healed by truncation

    # appends continue from the healed tail
    j2 = RequestJournal(path)
    j2.append_now({"type": "tokens", "cid": 9, "toks": [9]})
    j2.close()
    assert replay_journal(path).records == 3


def test_journal_compaction_retires_finished(tmp_path):
    from flexflow_tpu.serve.cluster.server import gen_to_wire

    path = str(tmp_path / "j.journal")
    j = RequestJournal(path, compact_threshold=1)
    for cid in (1, 2):
        j.append({"type": "submit", "cid": cid, "tokens": [cid, cid],
                  "prompt_len": 2, "gen": gen_to_wire(_gen()),
                  "session": None, "prompt": ""})
        j.append({"type": "tokens", "cid": cid, "toks": [40 + cid]})
    j.append_now({"type": "terminal", "cid": 1, "error": None})
    j.note_finished()
    assert j.should_compact()
    before = os.path.getsize(path)

    state = replay_journal(path)
    j.compact(live_records(None, state.unfinished()))
    assert not j.should_compact()
    j.close()
    assert os.path.getsize(path) < before

    replayed = replay_journal(path)
    assert list(replayed.entries) == [2]  # finished entry retired
    assert replayed.entries[2].flushed == [42]
    assert replayed.next_cid == 3


# ---------------------------------------------------------------------------
# kill-restart recovery


@pytest.mark.parametrize("kv_quant", [
    None,
    pytest.param("int8", marks=pytest.mark.slow),
])
def test_kill_restart_bitwise(tiny, tmp_path, kv_quant):
    """SIGKILL the manager mid-traffic, restart from the journal: every
    request terminal, greedy outputs BITWISE the uninterrupted run, and
    the pre-crash flushed (= streamed) tokens are a prefix of the
    recovered output — nothing lost, nothing duplicated."""
    cfg, params = tiny
    kw = sc_kwargs(replicas=2, router_policy="round_robin",
                   kv_quant=kv_quant)
    ref = _reference(tiny, replicas=2, router_policy="round_robin",
                     kv_quant=kv_quant)

    sc = ServingConfig(journal_dir=str(tmp_path), **kw)
    cm = ClusterManager.build(llama, cfg, params, sc)
    cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
    # step until the journal holds some flushed tokens (a MID-STREAM
    # kill), but far from completion
    for _ in range(40):
        cm.step()
        if any(cm.requests[c].output_tokens for c in cids):
            cm.step()
            break
    pre = {c: list(cm.requests[c].output_tokens) for c in cids}
    assert any(pre.values()), "nothing flushed before the kill"
    assert not all(cm._terminal(c) for c in cids), "killed too late"
    del cm  # the simulated SIGKILL: no drain, no close, no goodbyes

    cm2 = ClusterManager.recover(
        llama, cfg, params, ServingConfig(journal_dir=str(tmp_path), **kw)
    )
    assert cm2.stats.manager_recoveries == 1
    assert cm2.stats.journal_replayed == len(PROMPTS)
    got = _finish(cm2, cids)
    assert got == ref, "recovered outputs diverged from the " \
                       "uninterrupted run"
    for i, c in enumerate(cids):
        assert got[i][:len(pre[c])] == pre[c], (
            "tokens streamed before the crash were not a prefix of the "
            "recovered output (duplicate/lost tokens across restart)"
        )
        assert cm2.result(c).error is None
    cm2.check_no_leaks()
    no_held_slots(cm2)


def test_kill_restart_with_torn_tail(tiny, tmp_path):
    """A crash mid-journal-write leaves a torn tail; recovery truncates
    it and the (at most one flush point of) lost deltas regenerate
    bitwise through recompute."""
    cfg, params = tiny
    kw = sc_kwargs(replicas=2, router_policy="round_robin")
    ref = _reference(tiny, replicas=2, router_policy="round_robin")
    sc = ServingConfig(journal_dir=str(tmp_path), **kw)
    cm = ClusterManager.build(llama, cfg, params, sc)
    cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
    for _ in range(8):
        cm.step()
    del cm
    path = str(tmp_path / "requests.journal")
    with open(path, "ab") as f:
        f.write(b"\xde\xad\xbe\xef torn mid-write")
    cm2 = ClusterManager.recover(
        llama, cfg, params, ServingConfig(journal_dir=str(tmp_path), **kw)
    )
    assert _finish(cm2, cids) == ref
    cm2.check_no_leaks()


def test_recover_preserves_terminal_results(tiny, tmp_path):
    """A restart after everything finished still answers result() for
    every journaled request — terminal records rehydrate."""
    cfg, params = tiny
    kw = sc_kwargs(replicas=2, router_policy="round_robin")
    sc = ServingConfig(journal_dir=str(tmp_path), **kw)
    cm = ClusterManager.build(llama, cfg, params, sc)
    ref = _outputs(cm)
    cids = sorted(cm.requests)
    del cm
    cm2 = ClusterManager.recover(
        llama, cfg, params, ServingConfig(journal_dir=str(tmp_path), **kw)
    )
    assert cm2.stats.journal_replayed == 0
    for i, c in enumerate(cids):
        res = cm2.result(c)
        assert res.error is None
        assert list(res.output_tokens) == ref[i]
        assert cm2.requests[c].status is RequestStatus.COMPLETED
    # and the recovered manager still serves new traffic
    fresh = cm2.generate([[4, 4, 4, 4]], max_new_tokens=4)
    assert fresh[0].error is None and len(fresh[0].output_tokens) == 4


def test_recover_requires_journal_dir(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="journal_dir"):
        ClusterManager.recover(
            llama, cfg, params, ServingConfig(**sc_kwargs(replicas=2))
        )


def test_manager_crash_fault_kind(tiny, tmp_path):
    """FaultPlan "manager_crash": the scripted checkpoint-kill raises
    InjectedManagerCrash out of step() at the scripted CLUSTER step,
    exactly once; recovery (re-attaching the SAME injector, whose fired
    state survives) finishes the run bitwise."""
    cfg, params = tiny
    kw = sc_kwargs(replicas=2, router_policy="round_robin")
    ref = _reference(tiny, replicas=2, router_policy="round_robin")

    sc = ServingConfig(journal_dir=str(tmp_path), **kw)
    cm = ClusterManager.build(llama, cfg, params, sc)
    injector = cm.attach_faults(FaultPlan([
        Fault("manager_crash", replica=0, step=5),
    ]))
    cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
    with pytest.raises(InjectedManagerCrash):
        for _ in range(200):
            cm.step()
    assert [f["kind"] for f in injector.fired] == ["manager_crash"]
    assert cm._step_counter == 5
    del cm

    cm2 = ClusterManager.recover(
        llama, cfg, params, ServingConfig(journal_dir=str(tmp_path), **kw)
    )
    # the SAME injector re-attaches: its manager_crash already fired, so
    # the recovered manager runs the rest of the plan without re-dying
    cm2.attach_faults(injector)
    assert _finish(cm2, cids) == ref
    assert len(injector.fired) == 1
    cm2.check_no_leaks()


def test_fault_plan_random_kind_flags():
    """FaultPlan.random stays on REPLICA_KINDS by default; the opt-in
    flags widen the pool to transport/process kinds deterministically."""
    plan = FaultPlan.random(7, 3, n_faults=40)
    assert {f.kind for f in plan} <= set(REPLICA_KINDS)
    wide = FaultPlan.random(7, 3, n_faults=200, include_transport=True,
                            include_process=True)
    kinds = {f.kind for f in wide}
    assert kinds & set(TRANSPORT_KINDS)
    assert kinds & set(PROCESS_KINDS)
    assert FaultPlan.random(
        7, 3, n_faults=200, include_transport=True, include_process=True
    ).to_json() == wide.to_json()


def test_sigkill_rejected_off_socket(tiny):
    cm = _cluster(tiny, replicas=2, replica_transport="loopback")
    with pytest.raises(ValueError, match="sigkill"):
        cm.attach_faults(FaultPlan([Fault("sigkill", replica=1, step=3)]))


# ---------------------------------------------------------------------------
# live reconfiguration: scale_out / scale_in / set_pools


FAMILY = [
    [7, 7, 7, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17],
    [7, 7, 7, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 18],
    [7, 7, 7, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 19],
]


def test_scale_out_warm_vs_cold(tiny, tmp_path):
    """A scaled-out replica enters routing WARM: the donor's prefix
    subtrees ship over the export/import path before the first
    placement, so its post-join hit rate beats a cold join."""
    cfg, params = tiny

    def run(warm):
        sc = ServingConfig(
            journal_dir=str(tmp_path / ("w" if warm else "c")),
            prefix_caching=True, **sc_kwargs(replicas=1),
        )
        cm = ClusterManager.build(llama, cfg, params, sc)
        cm.generate([FAMILY[0]], max_new_tokens=4)
        pos = cm.scale_out(warm=warm)
        assert pos == 1 and len(cm.replicas) == 2
        assert len(cm.router.replicas) == 2  # entered routing
        score = cm.replicas[1].prefix_score(FAMILY[1])
        # route a family relative: warm joins can win it by prefix
        outs = cm.generate(FAMILY[1:], max_new_tokens=4)
        assert all(r.error is None for r in outs)
        hits = cm.replicas[1].rm.stats.prefix_hits
        assert cm.stats.scale_outs == 1
        cm.check_no_leaks()
        return score, hits

    warm_score, warm_hits = run(warm=True)
    cold_score, cold_hits = run(warm=False)
    assert warm_score > 0 and cold_score == 0
    assert warm_hits > cold_hits, (
        f"warm join served no more prefix hits than cold "
        f"({warm_hits} vs {cold_hits})"
    )


def test_scale_in_drains_clean(tiny, tmp_path):
    """scale_in fully drains: the router places NOTHING on a DRAINING
    replica, in-flight work finishes where it is, and the replica
    retires leak-free with zero held slots — while its already-terminal
    results stay readable after it left the membership."""
    cfg, params = tiny
    sc = ServingConfig(journal_dir=str(tmp_path),
                       **sc_kwargs(replicas=2,
                                   router_policy="round_robin"))
    cm = ClusterManager.build(llama, cfg, params, sc)
    ref = _reference(tiny, replicas=2, router_policy="round_robin")
    cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
    on_one = [c for c in cids if cm.requests[c].replica == 1]
    assert on_one, "round robin should have placed work on replica 1"
    for _ in range(3):
        cm.step()
    cm.begin_scale_in(1)
    # placements after the drain began all land on the survivor
    late = [cm.submit(p, max_new_tokens=4) for p in ([8, 9, 10], [2, 4])]
    assert all(cm.requests[c].replica == 0 for c in late)
    retiree = cm.replicas[1]
    out = _finish(cm, cids + late)
    assert out[:len(cids)] == ref  # drained requests finished bitwise
    assert len(cm.replicas) == 1 and cm._retired
    assert cm.stats.scale_ins == 1
    retiree.check_no_leaks()  # the RETIRED pool audits clean
    assert retiree.rm.hold_finished == set()
    cm.check_no_leaks()
    # results that lived on the retiree re-homed to the cluster record
    for c in on_one:
        assert cm.requests[c].status is RequestStatus.COMPLETED
        assert list(cm.result(c).output_tokens) == ref[cids.index(c)]


def test_scale_in_sessions_repin_warm(tiny, tmp_path):
    """Drain and DOWN re-home sessions through the ONE
    drop_replica_sessions flow — and a DRAINING replica's multi-turn
    sessions land WARM on survivors (prefix hit > 0 post-drain),
    because the retiree's tree ships to the heir before it leaves."""
    cfg, params = tiny
    sc = ServingConfig(prefix_caching=True, journal_dir=str(tmp_path),
                       **sc_kwargs(replicas=2))
    cm = ClusterManager.build(llama, cfg, params, sc)
    # turn 1 pins the session on replica 0 (universal miss →
    # least-loaded → lowest index)
    turn1 = cm.generate([FAMILY[0]], max_new_tokens=4,
                        session_ids=["chat"])
    transcript = FAMILY[0] + list(turn1[0].output_tokens)
    assert cm.router.sessions["chat"] == 0
    cm.scale_in(0)
    assert "chat" not in cm.router.sessions  # dropped by the drain
    survivor = cm.replicas[0]
    before = survivor.rm.stats.prefix_hit_tokens
    turn2 = cm.generate([transcript + [50, 51]], max_new_tokens=4,
                        session_ids=["chat"])
    assert turn2[0].error is None
    assert cm.router.sessions["chat"] == 0  # re-pinned on the survivor
    assert survivor.rm.stats.prefix_hit_tokens > before, (
        "the re-pinned session landed COLD — the retiree's tree did "
        "not re-home"
    )


def test_scale_in_validation(tiny):
    cm = _cluster(tiny, replicas=2)
    with pytest.raises(ValueError, match="out of range"):
        cm.begin_scale_in(7)
    cm.begin_scale_in(1)
    with pytest.raises(ValueError, match="already draining"):
        cm.begin_scale_in(1)
    with pytest.raises(ValueError, match="no routable replica"):
        cm.begin_scale_in(0)
    cm2 = _cluster(tiny, replicas=2, prefill_replicas=1,
                   decode_replicas=1)
    with pytest.raises(ValueError, match="empty the prefill pool"):
        cm2.begin_scale_in(0)
    with pytest.raises(ValueError, match="mixed"):
        cm2.scale_out(role="mixed")
    cm3 = _cluster(tiny, replicas=1)
    with pytest.raises(ValueError, match="set_pools"):
        cm3.scale_out(role="decode")


def test_set_pools_under_traffic_bitwise(tiny, tmp_path):
    """Flip an all-mixed pair into disaggregated prefill/decode pools
    WITH requests in flight: the in-flight batch finishes bitwise the
    static all-mixed run (live requests keep their homes), and the next
    batch is bitwise the statically-disaggregated run (placements see
    the new pools) — migrations prove the split went live."""
    cfg, params = tiny
    kw = sc_kwargs(replicas=2, router_policy="round_robin")
    ref_mixed = _reference(tiny, replicas=2, router_policy="round_robin")
    ref_disagg = _reference(tiny, replicas=2, prefill_replicas=1,
                            decode_replicas=1)

    sc = ServingConfig(journal_dir=str(tmp_path), **kw)
    cm = ClusterManager.build(llama, cfg, params, sc)
    cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
    for _ in range(3):
        cm.step()
    cm.set_pools({0: "prefill", 1: "decode"})  # mid-flight
    assert cm.disaggregated
    assert _finish(cm, cids) == ref_mixed
    assert cm.stats.migrations == 0  # in-flight work never migrated
    assert _outputs(cm) == ref_disagg
    assert cm.stats.migrations > 0  # the new batch rode the split
    assert cm.stats.pool_flips == 1
    cm.check_no_leaks()
    no_held_slots(cm)
    # and back to mixed once nothing is in flight
    cm.set_pools({0: "mixed", 1: "mixed"})
    assert not cm.disaggregated
    assert _outputs(cm) == ref_mixed


def test_set_pools_validation(tiny):
    cm = _cluster(tiny, replicas=2, prefill_replicas=1,
                  decode_replicas=1)
    with pytest.raises(ValueError, match="BOTH pools"):
        cm.set_pools({1: "prefill"})
    with pytest.raises(ValueError, match="mix 'mixed'"):
        cm.set_pools({0: "mixed"})
    with pytest.raises(ValueError, match="out of range"):
        cm.set_pools({9: "decode"})
    cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS[:2]]
    with pytest.raises(ValueError, match="strand"):
        cm.set_pools({0: "mixed", 1: "mixed"})
    _finish(cm, cids)
    dense = _cluster(tiny, replicas=2, kv_layout="dense")
    with pytest.raises(ValueError, match="paged"):
        dense.set_pools({0: "prefill", 1: "decode"})


def test_reconfigured_membership_survives_recovery(tiny, tmp_path):
    """scale_out commits a members snapshot — a manager crash AFTER the
    commit recovers the 2-replica membership (not the config's 1), and
    the in-flight requests finish bitwise."""
    cfg, params = tiny
    kw = sc_kwargs(replicas=1)
    ref = _reference(tiny, replicas=1)
    sc = ServingConfig(journal_dir=str(tmp_path), **kw)
    cm = ClusterManager.build(llama, cfg, params, sc)
    cm.scale_out(warm=False)
    cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
    for _ in range(4):
        cm.step()
    del cm
    cm2 = ClusterManager.recover(
        llama, cfg, params, ServingConfig(journal_dir=str(tmp_path), **kw)
    )
    assert len(cm2.replicas) == 2, "journaled scale_out lost in recovery"
    assert cm2.serving.replicas == 2
    assert _finish(cm2, cids) == ref
    cm2.check_no_leaks()


def test_reconfig_and_recovery_tracer_events(tiny, tmp_path):
    """The obs tracer gains drain/retire/scale_out/set_pools and
    recover/replay events on the router lane."""
    from flexflow_tpu.obs import attach_observability

    cfg, params = tiny
    kw = sc_kwargs(replicas=1)
    sc = ServingConfig(journal_dir=str(tmp_path), **kw)
    cm = ClusterManager.build(llama, cfg, params, sc)
    buf = attach_observability(cm)
    cm.scale_out(warm=False)
    cids = [cm.submit(p, max_new_tokens=4) for p in PROMPTS[:2]]
    cm.begin_scale_in(1)
    _finish(cm, cids)
    assert len(cm.replicas) == 1
    names = [e["name"] for e in buf.events]
    for want in ("scale_out", "drain_begin", "retire"):
        assert want in names, f"missing tracer event {want!r}"
    del cm
    cm2 = ClusterManager.recover(
        llama, cfg, params, ServingConfig(journal_dir=str(tmp_path), **kw)
    )
    buf2 = attach_observability(cm2)
    cm2.generate([[5, 5, 5]], max_new_tokens=2)
    names2 = [e["name"] for e in buf2.events]
    assert "recover" in names2 and "replay" in names2


# ---------------------------------------------------------------------------
# chaos: replica death + manager death in one seeded run


@pytest.mark.parametrize("seed", [11, pytest.param(29, marks=pytest.mark.slow)])
def test_chaos_replica_crash_plus_manager_crash(tiny, tmp_path, seed):
    """One seeded run containing BOTH failure classes this repo can
    now absorb: a replica crash (failover via recompute) and a manager
    crash (journal recovery). Every request reaches a terminal state,
    survivors are leak-free with zero held slots, and the recovered
    manager reuses the SAME injector so fired faults stay fired."""
    cfg, params = tiny
    kw = sc_kwargs(replicas=3, router_policy="round_robin",
                   replica_transport="loopback", failover_retries=4)
    sc = ServingConfig(journal_dir=str(tmp_path / str(seed)), **kw)
    cm = ClusterManager.build(llama, cfg, params, sc)
    plan = FaultPlan(
        list(FaultPlan.random(seed, 3, n_faults=2,
                              kinds=("crash", "transient")))
        + [Fault("manager_crash", replica=0, step=6 + seed % 5)]
    )
    injector = cm.attach_faults(plan)
    prompts = PROMPTS + [[5, 5, 5, 5, 5], [13, 12, 11]]
    cids = [cm.submit(p, max_new_tokens=6) for p in prompts]
    recoveries = 0
    steps = 0
    while any(not cm._terminal(c) for c in cids):
        steps += 1
        assert steps < 3000, "chaos run hung"
        try:
            progressed = cm.step()
        except InjectedManagerCrash:
            del cm
            cm = ClusterManager.recover(
                llama, cfg, params,
                ServingConfig(journal_dir=str(tmp_path / str(seed)), **kw),
            )
            cm.attach_faults(injector)
            recoveries += 1
            continue
        if not progressed:
            cm.drain()
            if any(not cm._terminal(c) for c in cids):
                break
    cm.drain()
    assert recoveries == 1
    assert cm.stats.manager_recoveries == 1
    for c in cids:
        assert cm.requests[c].status in TERMINAL_STATUSES, (
            f"request {c} never reached a terminal state"
        )
    if injector is not None:
        injector.release_all()
    cm.check_no_leaks()
    no_held_slots(cm)


# ---------------------------------------------------------------------------
# subprocess variants: the manager dies, the replica SERVERS keep running


def _spawn_server(serving_dict, index=0, seed=0):
    import json
    import subprocess
    import sys
    import time

    spec = {
        "family": "llama",
        "config": {"preset": "tiny", "dtype": "float32"},
        "seed": seed,
        "index": index,
        "serving": serving_dict,
    }
    proc = subprocess.Popen(
        [sys.executable, "-m", "flexflow_tpu.serve.cluster.server",
         "--port", "0", "--spec", json.dumps(spec)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    port = None
    deadline = time.time() + 180
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            time.sleep(0.1)
            if proc.poll() is not None:
                raise RuntimeError("replica server died during startup")
            continue
        if line.startswith("FLEXFLOW_REPLICA_SERVER PORT="):
            port = int(line.strip().rpartition("=")[2])
            break
    if port is None:
        proc.kill()
        raise RuntimeError("replica server never announced its port")
    return proc, port


@pytest.mark.slow
def test_subprocess_kill_restart_reconnects(tiny, tmp_path):
    """The flagship multi-process recovery: the manager process dies
    but its subprocess replica servers keep running — recover()
    re-dials them, rebuilds the client mirror from envelopes, abandons
    the orphaned scheduler state (the seq cache keeps the replayed
    RPCs at-most-once) and re-admits the journaled requests, bitwise
    the uninterrupted socket run."""
    cfg, params = tiny
    procs_ports = [_spawn_server(sc_kwargs(cache_dtype="float32"), index=i)
                   for i in range(2)]
    try:
        eps = tuple(f"127.0.0.1:{port}" for _, port in procs_ports)
        kw = sc_kwargs(replicas=2, router_policy="round_robin",
                       replica_transport="socket",
                       replica_endpoints=eps, rpc_deadline_s=120.0)
        # uninterrupted reference on the SAME servers (abandon between
        # runs keeps schedulers clean; greedy outputs are stateless)
        cm_ref = ClusterManager.build(
            llama, cfg, params, ServingConfig(**kw))
        ref = _outputs(cm_ref)
        for rep in cm_ref.replicas:
            rep.abandon()
            rep.close()  # free the server's serve-one-client loop
        del cm_ref

        sc = ServingConfig(journal_dir=str(tmp_path), **kw)
        cm = ClusterManager.build(llama, cfg, params, sc)
        cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
        for _ in range(6):
            cm.step()
        # the simulated SIGKILL: the OS closes a dead process's TCP
        # connections — the single-client server accept loops must see
        # that, or the recovered manager's dial waits in the backlog
        for rep in cm.replicas:
            rep.transport.drop_connection()
        del cm  # manager dead; the server processes live on

        cm2 = ClusterManager.recover(
            llama, cfg, params,
            ServingConfig(journal_dir=str(tmp_path), **kw),
        )
        for proc, _ in procs_ports:
            assert proc.poll() is None, "a replica server died"
        got = _finish(cm2, cids)
        assert got == ref, "recovered socket cluster diverged bitwise"
        cm2.check_no_leaks()
        snap = cm2.cluster_stats()
        assert snap["manager_recoveries"] == 1
        cm2.replicas[0]._rpc("shutdown", {})
        cm2.replicas[1]._rpc("shutdown", {})
    finally:
        for proc, _ in procs_ports:
            proc.terminate()
            proc.wait(timeout=30)


@pytest.mark.slow
def test_chaos_sigkill_server_and_manager_crash(tiny, tmp_path):
    """Process-death chaos, not surface-level raises: one subprocess
    replica server is REALLY SIGKILL'd (registered pid) while a
    scripted manager crash forces a journal recovery in the same run —
    every request terminal, the survivor leak-free."""
    cfg, params = tiny
    procs_ports = [_spawn_server(sc_kwargs(cache_dtype="float32"), index=i)
                   for i in range(2)]
    try:
        eps = tuple(f"127.0.0.1:{port}" for _, port in procs_ports)
        kw = sc_kwargs(
            replicas=2, router_policy="round_robin",
            replica_transport="socket", replica_endpoints=eps,
            rpc_deadline_s=120.0, rpc_retries=1, failover_retries=4,
            heartbeat_gap_steps=2,
        )
        sc = ServingConfig(journal_dir=str(tmp_path), **kw)
        cm = ClusterManager.build(llama, cfg, params, sc)
        plan = FaultPlan([
            Fault("sigkill", replica=1, step=3),
            Fault("manager_crash", replica=0, step=8),
        ])
        injector = cm.attach_faults(plan)
        injector.register_process(1, procs_ports[1][0].pid)
        cids = [cm.submit(p, max_new_tokens=6) for p in PROMPTS]
        steps = 0
        while any(not cm._terminal(c) for c in cids):
            steps += 1
            assert steps < 2000, "chaos run hung"
            try:
                progressed = cm.step()
            except InjectedManagerCrash:
                # the OS would close a SIGKILL'd manager's sockets —
                # simulate that so the surviving single-client server
                # accepts the recovered manager's dial
                for rep in cm.replicas:
                    rep.transport.drop_connection()
                del cm
                cm = ClusterManager.recover(
                    llama, cfg, params,
                    ServingConfig(journal_dir=str(tmp_path), **kw),
                )
                cm.attach_faults(injector)
                continue
            if not progressed:
                cm.drain()
                if any(not cm._terminal(c) for c in cids):
                    break
        cm.drain()
        assert procs_ports[1][0].poll() is not None, (
            "the sigkill fault never killed the server process"
        )
        for c in cids:
            assert cm.requests[c].status in TERMINAL_STATUSES
        assert len(injector.fired) >= 2
        # the survivor audits clean; the killed server is gone with its
        # process (exactly the multi-host story)
        cm.replicas[0].check_no_leaks()
        cm.replicas[0]._rpc("shutdown", {})
    finally:
        for proc, _ in procs_ports:
            if proc.poll() is None:
                proc.terminate()
                proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# PR-19 satellite: the SIGKILL-recovery path under the lock sanitizer —
# recovery must be BITWISE identical sanitizer-on vs -off, with zero
# findings over the whole kill/replay/finish sequence. Gate 14 selects
# this by the `locks_sanitizer` name fragment.


@pytest.mark.slow
def test_locks_sanitizer_kill_restart_bitwise(tiny, tmp_path):
    from flexflow_tpu.analysis.locks import (
        active_lock_sanitizer,
        disable_lock_sanitizer,
    )

    cfg, params = tiny

    def kill_and_recover(jdir, sanitizers):
        kw = sc_kwargs(replicas=2, router_policy="round_robin",
                       replica_transport="loopback",
                       sanitizers=sanitizers)
        sc = ServingConfig(journal_dir=jdir, **kw)
        cm = ClusterManager.build(llama, cfg, params, sc)
        cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
        for _ in range(40):
            cm.step()
            if any(cm.requests[c].output_tokens for c in cids):
                cm.step()
                break
        assert not all(cm._terminal(c) for c in cids), "killed too late"
        del cm  # simulated SIGKILL: no drain, no close, no goodbyes
        cm2 = ClusterManager.recover(
            llama, cfg, params, ServingConfig(journal_dir=jdir, **kw)
        )
        assert cm2.stats.manager_recoveries == 1
        got = _finish(cm2, cids)
        errs = [cm2.result(c).error for c in cids]
        cm2.check_no_leaks()
        return got, errs

    try:
        assert active_lock_sanitizer() is None
        base = kill_and_recover(str(tmp_path / "off"), ())
        assert active_lock_sanitizer() is None
        sanitized = kill_and_recover(str(tmp_path / "on"), ("locks",))
        san = active_lock_sanitizer()
        assert san is not None, "ServingConfig wiring did not enable"
        assert san.findings == [], "\n".join(san.findings)
        assert san.acquisitions > 0
        assert sanitized == base, (
            "lock sanitizer changed SIGKILL-recovery behavior"
        )
    finally:
        disable_lock_sanitizer()

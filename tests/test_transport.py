"""Replica RPC transport tests (serve/cluster/transport.py + remote.py
+ server.py): wire-codec byte-exactness, loopback-transported clusters
BITWISE the in-process PR-8/9 clusters (greedy + same-seed sampling,
page migration included), transport fault kinds
(drop/delay/disconnect/partition) riding the PR-9 health/failover
machinery, heartbeat-gap detection in deterministic cluster steps with
the one-observation-per-step guard, warm-standby adoption of a dead
replica's prefix families, and the subprocess replica server
(slow-marked; premerge gate 9 runs them unfiltered).
"""
import dataclasses
import json
import socket
import struct
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    ClusterManager,
    GenerationConfig,
    InferenceEngine,
    RequestManager,
    ServingConfig,
)
from flexflow_tpu.serve.cluster import (
    TRANSPORT_KINDS,
    ConnectionLost,
    DeadlineExceeded,
    Fault,
    FaultInjector,
    FaultPlan,
    FrameError,
    HealthState,
    LoopbackTransport,
    RemoteError,
    Replica,
    ReplicaServerCore,
    SocketTransport,
    TransportError,
)
from flexflow_tpu.serve.cluster.transport import (
    decode_frame,
    decode_value,
    encode_frame,
    encode_value,
    read_frame_from_socket,
)


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


def roundtrip(value):
    return decode_frame(encode_frame(value))


# ---------------------------------------------------------------------------
# wire codec units (satellite: every message + a migrated page,
# byte-exact; malformed frames raise, never hang)


def test_codec_roundtrip_scalars_and_containers():
    cases = [
        None, True, False, 0, -1, 2**62, -(2**62), 2**80, -(2**80),
        3.5, -0.0, float("inf"), "", "tøkens", b"", b"\x00\xff raw",
        [], [1, [2, [3]]], {}, {"a": 1, 2: "b", "nest": {"x": [None]}},
    ]
    for case in cases:
        assert roundtrip(case) == case, case
    # tuples arrive as lists (the codec's one normalization)
    assert roundtrip((1, 2, 3)) == [1, 2, 3]


def test_codec_roundtrip_migrated_page_byte_exact():
    """The load-bearing arrays of a migrated KV page: fp pages, int8
    codes, int4 packed-nibble uint8 codes, f32 quant scale rows, int32
    generic-decoder pos lines — all byte-exact through the codec."""
    rng = np.random.default_rng(7)
    page = {
        "k_fp": rng.standard_normal((1, 16, 2, 8), dtype=np.float32),
        "k_int8": rng.integers(-128, 128, (1, 16, 2, 8), dtype=np.int8),
        "k_int4": rng.integers(0, 256, (1, 16, 2, 4), dtype=np.uint8),
        "k_scale": rng.standard_normal((1, 2), dtype=np.float32),
        "pos": rng.integers(0, 4096, (1, 16), dtype=np.int32),
    }
    out = roundtrip({"pages": [page]})["pages"][0]
    assert set(out) == set(page)
    for name, arr in page.items():
        got = out[name]
        assert got.dtype == arr.dtype and got.shape == arr.shape, name
        assert got.tobytes() == arr.tobytes(), f"{name} not byte-exact"


def test_codec_roundtrip_replica_surface_messages():
    """One representative frame per RPC the Replica surface speaks."""
    gen = {"do_sample": False, "temperature": 0.8, "topp": 0.95,
           "topk": 0, "max_new_tokens": 8, "stop_token_ids": [2],
           "num_beams": 1, "length_penalty": 1.0}
    page = {"k": np.arange(8, dtype=np.int8)}
    messages = [
        {"seq": 1, "method": "hello", "args": {}},
        {"seq": 2, "method": "heartbeat", "args": {}},
        {"seq": 3, "method": "prefix_score", "args": {"tokens": [1, 2, 3]}},
        {"seq": 4, "method": "step", "args": {}},
        {"seq": 5, "method": "submit",
         "args": {"tokens": [4, 5], "gen": gen}},
        {"seq": 6, "method": "hold_on_finish", "args": {"rid": 3}},
        {"seq": 7, "method": "migrate_out", "args": {"rid": 3}},
        {"seq": 8, "method": "migrate_in",
         "args": {"tokens": [4, 5, 6], "prompt_len": 2, "prompt": "",
                  "page_size": 16, "pages": [page], "gen": gen}},
        {"seq": 9, "method": "import_tree",
         "args": {"entries": [{"parent": -1, "tokens": [1] * 16,
                               "payload": page}]}},
        {"seq": 10, "ok": True,
         "result": {"progressed": True,
                    "telemetry": {"stats": {"steps": 4}},
                    "updates": {7: {"status": "decoding",
                                    "tokens": [1, 2, 3], "error": None}}}},
        {"seq": 11, "ok": False,
         "error": {"type": "AssertionError", "msg": "leaked page 3"}},
    ]
    for msg in messages:
        got = roundtrip(msg)
        flat_in = json.dumps(msg, default=lambda a: a.tolist(), sort_keys=True)
        flat_out = json.dumps(got, default=lambda a: a.tolist(),
                              sort_keys=True)
        assert flat_in == flat_out, msg["seq"]


def test_codec_rejects_unencodable():
    with pytest.raises(FrameError, match="unencodable"):
        encode_frame(object())


def test_malformed_frames_raise_typed_errors():
    good = encode_frame({"seq": 1, "method": "x", "args": {}})
    with pytest.raises(TransportError, match="magic"):
        decode_frame(b"XX" + good[2:])
    with pytest.raises(TransportError, match="version"):
        decode_frame(good[:2] + b"\x09" + good[3:])
    with pytest.raises(TransportError, match="truncated"):
        decode_frame(good[:-3])
    with pytest.raises(TransportError, match="short frame"):
        decode_frame(good[:4])
    with pytest.raises(TransportError, match="trailing"):
        decode_value(good[7:] + b"\x00")
    # a corrupted length prefix can never drive a giant allocation
    huge = good[:3] + struct.pack("!I", 1 << 31) + good[7:]
    with pytest.raises(TransportError, match="MAX_FRAME_BYTES"):
        decode_frame(huge)
    with pytest.raises(TransportError, match="tag"):
        decode_value(b"\x7f")


def test_socket_read_never_hangs_past_deadline():
    """A silent peer costs exactly the deadline, then a typed raise —
    the malformed/truncated-frame contract's socket half."""
    listener = socket.create_server(("127.0.0.1", 0))
    port = listener.getsockname()[1]
    accepted = []
    t = threading.Thread(
        target=lambda: accepted.append(listener.accept()), daemon=True
    )
    t.start()
    client = socket.create_connection(("127.0.0.1", port), timeout=5.0)
    client.settimeout(0.2)
    t0 = time.perf_counter()
    with pytest.raises(DeadlineExceeded):
        read_frame_from_socket(client)
    assert time.perf_counter() - t0 < 2.0
    # a peer that closes mid-frame raises ConnectionLost, not a hang
    t.join(timeout=5.0)
    conn, _ = accepted[0]
    conn.sendall(encode_frame({"x": 1})[:5])
    conn.close()
    client.settimeout(2.0)
    with pytest.raises(ConnectionLost):
        read_frame_from_socket(client)
    client.close()
    listener.close()


def test_loopback_transport_roundtrip_and_remote_errors():
    def dispatch(req):
        if req["method"] == "boom":
            return {"seq": req["seq"], "ok": False,
                    "error": {"type": "ValueError", "msg": "nope"}}
        return {"seq": req["seq"], "ok": True,
                "result": {"echo": req["args"]}}

    tp = LoopbackTransport(dispatch)
    out = tp.call(1, "echo", {"x": [1, 2]}, deadline_s=1.0)
    assert out == {"echo": {"x": [1, 2]}}
    assert tp.bytes_sent > 0 and tp.bytes_received > 0
    with pytest.raises(RemoteError, match="ValueError: nope"):
        tp.call(2, "boom", {}, deadline_s=1.0)


# ---------------------------------------------------------------------------
# FaultPlan transport kinds (satellite: schema + determinism + the
# loud rejection against in-process replicas)


def test_fault_plan_transport_kinds_schema_and_json():
    plan = FaultPlan([
        Fault("drop", replica=0, step=3, count=2),
        Fault("delay", replica=1, step=4, count=3, seconds=0.25),
        Fault("disconnect", replica=0, step=6),
        Fault("partition", replica=1, step=8, count=5),
    ])
    back = FaultPlan.from_json(plan.to_json())
    assert list(back) == list(plan)
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("packetloss", replica=0, step=1)


def test_fault_plan_random_transport_determinism():
    a = FaultPlan.random(11, 3, kinds=TRANSPORT_KINDS, n_faults=4)
    b = FaultPlan.random(11, 3, kinds=TRANSPORT_KINDS, n_faults=4)
    assert list(a) == list(b)
    assert all(f.kind in TRANSPORT_KINDS for f in a)
    # the default stays on the PR-9 replica kinds
    assert all(f.kind not in TRANSPORT_KINDS for f in FaultPlan.random(3, 2))


def test_transport_faults_rejected_on_inproc_cluster(tiny):
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(replicas=2))
    cm = ClusterManager.build(llama, cfg, params, sc)
    with pytest.raises(ValueError, match="transport kinds"):
        cm.attach_faults(FaultPlan([Fault("partition", replica=1, step=1)]))
    # replica kinds still attach fine
    cm.attach_faults(FaultPlan([Fault("transient", replica=1, step=999)]))


def test_oom_fault_rejected_on_socket_cluster(tiny):
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(
        replicas=1, replica_transport="socket",
        replica_endpoints=("127.0.0.1:1",),
    ))
    # socket build dials lazily — no server needed to validate attach
    cm = ClusterManager.build(llama, cfg, params, sc)
    with pytest.raises(ValueError, match="oom"):
        cm.attach_faults(FaultPlan([Fault("oom", replica=0, step=1)]))


def test_transport_config_validation():
    with pytest.raises(ValueError, match="replica_transport"):
        ServingConfig(**sc_kwargs(replica_transport="carrier-pigeon")
                      ).validate_cluster()
    with pytest.raises(ValueError, match="replica_endpoints"):
        ServingConfig(**sc_kwargs(replicas=2, replica_transport="socket")
                      ).validate_cluster()
    with pytest.raises(ValueError, match="standby_replicas"):
        ServingConfig(**sc_kwargs(standby_replicas=-1)).validate_cluster()
    with pytest.raises(ValueError, match="disaggregated"):
        ServingConfig(**sc_kwargs(
            replicas=2, prefill_replicas=1, decode_replicas=1,
            standby_replicas=1,
        )).validate_cluster()
    with pytest.raises(ValueError, match="rpc_deadline_s"):
        ServingConfig(**sc_kwargs(rpc_deadline_s=0.0)).validate_cluster()
    with pytest.raises(ValueError, match="heartbeat_gap_steps"):
        ServingConfig(**sc_kwargs(heartbeat_gap_steps=0)).validate_cluster()


def test_server_seq_cache_makes_retries_idempotent(tiny):
    """A retried RPC whose response was lost must not re-execute: same
    seq → the cached response replays, the replica steps once."""
    cfg, params = tiny
    rep = Replica.build(0, llama, cfg, params,
                        ServingConfig(**sc_kwargs()))
    core = ReplicaServerCore(rep)
    rep.rm.submit(PROMPTS[0], max_new_tokens=2)
    req = {"seq": 5, "method": "step", "args": {}}
    first = core.dispatch(dict(req))
    assert rep.steps_taken == 1
    again = core.dispatch(dict(req))
    assert rep.steps_taken == 1, "duplicate seq re-executed the step"
    assert again is first


# ---------------------------------------------------------------------------
# loopback cluster == in-process cluster, bitwise


def _outputs(cm, gen=None, n_new=8, prompts=PROMPTS):
    return [
        r.output_tokens
        for r in cm.generate(prompts, gen=gen, max_new_tokens=n_new)
    ]


def _cluster(tiny, transport, **kw):
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(replica_transport=transport, **kw))
    return ClusterManager.build(llama, cfg, params, sc)


_REFERENCE = {}


def _reference(tiny, transport, **kw):
    """``_outputs`` of a fault-free cluster, computed once a
    configuration: greedy decoding is deterministic (what the tests here
    assert bitwise), and every cluster built compiles its replicas' step
    programs again (ROADMAP A13)."""
    key = (transport,) + tuple(
        sorted((k, v) for k, v in kw.items() if v is not None))
    if key not in _REFERENCE:
        _REFERENCE[key] = _outputs(_cluster(tiny, transport, **kw))
    return _REFERENCE[key]


@pytest.mark.parametrize("kv_quant", [
    None,
    pytest.param("int8", marks=pytest.mark.slow),
    pytest.param("int4", marks=pytest.mark.slow),
])
def test_loopback_cluster_bitwise_inproc(tiny, kv_quant):
    kw = dict(replicas=2, router_policy="round_robin", kv_quant=kv_quant)
    ref = _reference(tiny, "inproc", **kw)
    cm = _cluster(tiny, "loopback", **kw)
    got = _outputs(cm)
    assert got == ref, "loopback-transported cluster diverged bitwise"
    cm.check_no_leaks()
    snap = cm.cluster_stats()
    assert snap["wire_bytes_sent"] > 0 and snap["wire_bytes_received"] > 0
    assert snap["rpc_errors"] == 0


def test_loopback_cluster_bitwise_sampling(tiny):
    """Same-seed SAMPLING parity: the loopback cluster replays the
    exact dispatch sequence, so the RNG streams line up."""
    gen = GenerationConfig(do_sample=True, temperature=0.7, topk=8)
    ref = _outputs(_cluster(tiny, "inproc", replicas=2,
                            router_policy="round_robin"), gen=gen)
    got = _outputs(_cluster(tiny, "loopback", replicas=2,
                            router_policy="round_robin"), gen=gen)
    assert got == ref


@pytest.mark.parametrize("kv_quant", [
    None,
    pytest.param("int8", marks=pytest.mark.slow),
])
def test_loopback_disaggregated_migration_bitwise(tiny, kv_quant):
    """Prefill→decode page migration OVER THE WIRE: codes + quant scale
    rows round-trip the codec byte-exact, so disaggregated loopback
    generation is bitwise the in-process disaggregated cluster (which
    PR-8 proved bitwise the single replica)."""
    kw = dict(replicas=2, prefill_replicas=1, decode_replicas=1,
              kv_quant=kv_quant)
    ref = _reference(tiny, "inproc", **kw)
    cm = _cluster(tiny, "loopback", **kw)
    got = _outputs(cm)
    assert got == ref
    st = cm.cluster_stats()
    assert st["migrations"] == len(PROMPTS)
    assert st["migrated_bytes"] > 0
    cm.check_no_leaks()
    for rep in cm.replicas:
        assert rep.rm.hold_finished == set()


def test_loopback_one_replica_bitwise_bare_engine(tiny):
    cfg, params = tiny
    rm = RequestManager(
        InferenceEngine(llama, cfg, params, ServingConfig(**sc_kwargs()))
    )
    ref = [r.output_tokens for r in rm.generate(PROMPTS, max_new_tokens=8)]
    got = _outputs(_cluster(tiny, "loopback", replicas=1))
    assert got == ref


# ---------------------------------------------------------------------------
# transport robustness: deadlines/retries, fault kinds, health wiring


def test_drop_fault_absorbed_by_retries(tiny):
    """A lossy link (first attempt of each RPC dropped) is absorbed by
    the retry machinery: zero health observations, zero rpc_errors,
    outputs bitwise — the retries are visible in ClusterStats and
    mirrored per-request into ProfileInfo.transport_retries."""
    ref = _reference(tiny, "loopback", replicas=2,
                     router_policy="round_robin")
    cm = _cluster(tiny, "loopback", replicas=2,
                  router_policy="round_robin")
    cm.attach_faults(FaultPlan([
        Fault("drop", replica=0, step=1, count=1000),
        Fault("drop", replica=1, step=1, count=1000),
    ]))
    cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
    while any(not cm._terminal(c) for c in cids):
        if not cm.step():
            break
    cm.drain()
    outs = [cm.result(c).output_tokens for c in cids]
    assert outs == ref
    st = cm.cluster_stats()
    assert st["rpc_retries"] > 0
    assert st["rpc_errors"] == 0
    assert st["step_faults"] == 0
    assert cm.health_snapshot() == ["healthy", "healthy"]
    assert any(
        cm.result(c).profile.transport_retries > 0 for c in cids
    ), "transport retries were not mirrored into ProfileInfo"


def test_partition_trips_breaker_failover_bitwise(tiny):
    """A partitioned replica exhausts its RPC retries, the SAME health
    machine circuit-breaks it, and its requests fail over through
    recompute — greedy outputs bitwise the fault-free run (the PR-9
    contract, now over the wire)."""
    ref = _reference(tiny, "loopback", replicas=2,
                     router_policy="round_robin")
    cm = _cluster(tiny, "loopback", replicas=2,
                  router_policy="round_robin")
    cm.attach_faults(FaultPlan([
        Fault("partition", replica=1, step=2, count=1000),
    ]))
    got = _outputs(cm)
    assert got == ref
    st = cm.cluster_stats()
    assert st["rpc_errors"] > 0 and st["replica_down"] >= 1
    assert st["failovers"] >= 1
    assert cm.health[1].state is HealthState.DOWN
    cm.check_no_leaks()  # survivors only — DOWN pool excluded


def test_delay_fault_over_deadline_degrades_like_a_stall(tiny):
    """An injected link delay at/over rpc_deadline_s fails every
    attempt (DeadlineExceeded) — the replica degrades exactly like a
    stalled one: breaker trips, requests fail over, outputs bitwise."""
    ref = _reference(tiny, "loopback", replicas=2,
                     router_policy="round_robin")
    cm = _cluster(tiny, "loopback", replicas=2,
                  router_policy="round_robin", rpc_deadline_s=1.0)
    cm.attach_faults(FaultPlan([
        Fault("delay", replica=1, step=2, count=1000, seconds=5.0),
    ]))
    got = _outputs(cm)
    assert got == ref
    assert cm.health[1].state is HealthState.DOWN
    assert cm.cluster_stats()["failovers"] >= 1


def test_disconnect_reconnects_without_health_impact(tiny):
    ref = _reference(tiny, "loopback", replicas=2,
                     router_policy="round_robin")
    cm = _cluster(tiny, "loopback", replicas=2,
                  router_policy="round_robin")
    cm.attach_faults(FaultPlan([Fault("disconnect", replica=0, step=3)]))
    got = _outputs(cm)
    assert got == ref
    st = cm.cluster_stats()
    assert st["reconnects"] >= 1
    assert st["replica_down"] == 0 and st["replica_suspect"] == 0
    assert cm.health_snapshot() == ["healthy", "healthy"]


def test_heartbeat_gap_trips_idle_replica(tiny):
    """An IDLE remote replica whose transport dies is caught by
    heartbeat-gap detection — counted in deterministic CLUSTER steps,
    no wall clock anywhere — and circuit-breaks through the same
    machine."""
    cm = _cluster(tiny, "loopback", replicas=2, heartbeat_gap_steps=3)
    rep = cm.replicas[1]

    def dead_dispatch(request):
        raise ConnectionLost("link down")

    rep.transport.dispatch = dead_dispatch
    down_at = None
    for step in range(1, 12):
        cm.step()
        if cm.health[1].state is HealthState.DOWN and down_at is None:
            down_at = step
    assert down_at is not None, "gapped idle replica never tripped"
    st = cm.cluster_stats()
    assert st["heartbeat_gaps"] >= 2
    # gap observations start at gap_steps(3) and need
    # failure_threshold(2) consecutive ones: DOWN on cluster step 4
    assert down_at == 4, f"gap arithmetic drifted (down at {down_at})"
    assert cm.health_snapshot()[0] == "healthy"


def test_one_suspect_observation_per_step_guard(tiny):
    """Bugfix guard: a replica that is simultaneously inside a
    heartbeat gap AND returning RPC errors gets ONE health observation
    per cluster step — with failure_threshold=2 it must take two
    cluster steps to trip, exactly the PR-9 arithmetic, not one."""
    cm = _cluster(tiny, "loopback", replicas=2, heartbeat_gap_steps=1)
    cm.attach_faults(FaultPlan([
        Fault("partition", replica=1, step=1, count=1000),
    ]))
    # give the partitioned replica work so its step RPC errors while
    # the gap detector also fires (gap_steps=1: gapped from step 1)
    cm.submit(PROMPTS[0], max_new_tokens=4, session_id="pin0")
    cm.router.sessions["pin1"] = 1
    cm.submit(PROMPTS[1], max_new_tokens=4, session_id="pin1")
    cm.step()
    assert cm.stats.heartbeat_gaps >= 1, "gap did not co-fire"
    assert cm.health[1].state is HealthState.SUSPECT, (
        "double-counted observations tripped the breaker in one step"
    )
    assert cm.health[1].consecutive_failures == 1
    cm.step()
    assert cm.health[1].state is HealthState.DOWN
    # drain to terminal so nothing is left mid-failover
    cids = list(cm.requests)
    for _ in range(200):
        if all(cm._terminal(c) for c in cids):
            break
        cm.step()
    assert all(cm._terminal(c) for c in cids)


def test_transport_chaos_seeded_terminal_bitwise(tiny):
    """The acceptance chaos run: disconnect + partition + delay over a
    loopback 3-replica cluster — every request terminal (never a
    hang), zero leaks/held slots on survivors, greedy outputs bitwise
    the fault-free run, and the same plan fires the same sequence."""
    kw = dict(replicas=3, router_policy="round_robin",
              failover_retries=3)
    ref = _reference(tiny, "loopback", **kw)
    plan_json = FaultPlan([
        Fault("partition", replica=1, step=2, count=1000),
        Fault("delay", replica=0, step=3, count=3, seconds=0.25),
        Fault("disconnect", replica=2, step=4, count=2),
        Fault("drop", replica=0, step=5, count=3),
    ]).to_json()

    def run():
        cm = _cluster(tiny, "loopback", **kw)
        injector = cm.attach_faults(plan_json)
        cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
        for _ in range(500):
            if all(cm._terminal(c) for c in cids):
                break
            cm.step()
        cm.drain()
        assert all(cm._terminal(c) for c in cids), "request hung"
        outs = [cm.result(c).output_tokens for c in cids]
        errs = [cm.result(c).error for c in cids]
        cm.check_no_leaks()
        for pos, rep in enumerate(cm.replicas):
            if cm.health[pos].state is not HealthState.DOWN:
                assert rep.rm.hold_finished == set()
        fired = [(f["kind"], f["replica"], f["step"]) for f in
                 injector.fired]
        return outs, errs, fired

    outs_a, errs_a, fired_a = run()
    outs_b, errs_b, fired_b = run()
    assert fired_a == fired_b, "seeded chaos diverged between runs"
    assert outs_a == outs_b and errs_a == errs_b
    assert errs_a == [None] * len(PROMPTS)
    assert outs_a == ref, "chaos outputs diverged from fault-free"


# ---------------------------------------------------------------------------
# prefix-tree export/import + warm-standby adoption

FAMILY = [7, 7, 7, 7] + list(range(1, 17))


def test_prefix_tree_export_import_roundtrip(tiny):
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(prefix_caching=True))
    src = Replica.build(0, llama, cfg, params, sc)
    src.rm.generate([FAMILY, FAMILY[:12] + [31, 32, 33]],
                    max_new_tokens=4)
    pc = src.rm.prefix_cache
    assert pc.match_len(FAMILY + [99]) > 0
    entries = src.export_prefix_tree()
    assert entries and all(e["payload"] is not None for e in entries)
    # entries survive the wire codec byte-exact
    entries = decode_frame(encode_frame(entries))

    dst = Replica.build(1, llama, cfg, params, sc)
    adopted = dst.import_prefix_tree(entries)
    assert adopted == len(entries)
    dpc = dst.rm.prefix_cache
    assert dpc.match_len(FAMILY + [99]) == pc.match_len(FAMILY + [99])
    dst.check_no_leaks()
    # generation over the adopted (warm) tree is bitwise the cold run
    cold = Replica.build(2, llama, cfg, params, sc)
    probe = FAMILY + [40, 41]
    out_cold = [r.output_tokens
                for r in cold.rm.generate([probe], max_new_tokens=6)]
    out_warm = [r.output_tokens
                for r in dst.rm.generate([probe], max_new_tokens=6)]
    assert out_warm == out_cold
    assert dst.rm.stats.prefix_hits > 0


def test_prefix_tree_export_ships_host_spilled_blocks(tiny):
    """Host-resident (spilled) blocks ship their PR-7 tier bytes
    directly — the adopted tree serves them warm on the importer."""
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(
        prefix_caching=True, host_cache_bytes=1 << 20,
    ))
    src = Replica.build(0, llama, cfg, params, sc)
    src.rm.generate([FAMILY], max_new_tokens=4)
    pc = src.rm.prefix_cache
    assert pc._spill_one(), "nothing spilled"
    pc.harvest()
    assert pc.host_pages >= 1
    entries = decode_frame(encode_frame(src.export_prefix_tree()))
    dst = Replica.build(1, llama, cfg, params, sc)
    assert dst.import_prefix_tree(entries) == len(entries)
    assert dst.rm.prefix_cache.match_len(FAMILY + [99]) == (
        pc.match_len(FAMILY + [99])
    )
    dst.check_no_leaks()


def test_standby_adopts_dead_replicas_prefix_families(tiny):
    """The tentpole's warm-standby path: on a DOWN transition the
    standby imports the dead replica's radix tree over the transport,
    takes its routing position, and failover re-admissions land WARM
    (prefix score > 0 immediately) — outputs bitwise the fault-free
    cluster."""
    kw = dict(replicas=2, router_policy="prefix", prefix_caching=True)
    seed_prompts = [FAMILY, FAMILY[:12] + [31, 32, 33]]
    probe_prompts = [FAMILY + [40], FAMILY + [41]]

    ref_cm = _cluster(tiny, "loopback", **kw)
    ref_cm.generate(seed_prompts, max_new_tokens=4)
    ref = _outputs(ref_cm, prompts=probe_prompts, n_new=6)

    cm = _cluster(tiny, "loopback", standby_replicas=1, **kw)
    cm.generate(seed_prompts, max_new_tokens=4)
    scores = [rep.prefix_score(FAMILY + [40]) for rep in cm.replicas]
    victim = max(range(2), key=lambda i: scores[i])
    assert scores[victim] > 0
    cm.attach_faults(FaultPlan([Fault(
        "crash", replica=victim,
        step=cm.replicas[victim].steps_taken + 1,
    )]))
    got = _outputs(cm, prompts=probe_prompts, n_new=6)
    assert got == ref, "standby failover diverged from fault-free"
    st = cm.cluster_stats()
    assert st["standby_adoptions"] == 1
    adopted = cm.replicas[victim]
    assert adopted.index == 2, "standby did not take the position"
    assert adopted.prefix_score(FAMILY + [42]) > 0, (
        "standby joined cold — the dead replica's families were not "
        "adopted"
    )
    assert cm.health[victim].state is HealthState.HEALTHY
    assert not cm.standbys and len(cm._retired) == 1
    cm.check_no_leaks()


def test_standby_joins_cold_when_export_unreachable(tiny):
    """A PARTITIONED (truly unreachable) dead replica cannot ship its
    tree — the standby must still adopt the position (capacity
    replaced), just cold, and every request stays terminal."""
    kw = dict(replicas=2, router_policy="prefix", prefix_caching=True)
    cm = _cluster(tiny, "loopback", standby_replicas=1, **kw)
    cm.generate([FAMILY], max_new_tokens=4)
    scores = [rep.prefix_score(FAMILY + [40]) for rep in cm.replicas]
    victim = max(range(2), key=lambda i: scores[i])
    cm.attach_faults(FaultPlan([Fault(
        "partition", replica=victim,
        step=cm.replicas[victim].steps_taken + 1, count=1000,
    )]))
    cids = [cm.submit(p, max_new_tokens=6)
            for p in (FAMILY + [40], FAMILY + [41])]
    # drive to the adoption and check the COLD join right there —
    # completed failovers would re-seed the family on the standby and
    # mask a cold join
    for _ in range(100):
        cm.step()
        if cm.stats.standby_adoptions:
            break
    assert cm.stats.standby_adoptions == 1
    assert cm.replicas[victim].index == 2
    assert cm.replicas[victim].prefix_score(FAMILY + [42]) == 0, (
        "tree export over a partitioned transport should be impossible"
    )
    for _ in range(500):
        if all(cm._terminal(c) for c in cids):
            break
        cm.step()
    cm.drain()
    assert all(cm._terminal(c) for c in cids)
    assert all(cm.result(c).error is None for c in cids)


# ---------------------------------------------------------------------------
# telemetry


def test_cluster_stats_transport_fields(tiny):
    cm = _cluster(tiny, "loopback", replicas=2,
                  router_policy="round_robin")
    _outputs(cm, n_new=4)
    snap = cm.cluster_stats()
    for key in ("rpc_errors", "rpc_retries", "heartbeat_gaps",
                "reconnects", "standby_adoptions", "wire_bytes_sent",
                "wire_bytes_received"):
        assert key in snap, key
    assert snap["wire_bytes_sent"] > 0
    assert snap["wire_bytes_received"] > snap["wire_bytes_sent"], (
        "envelopes (telemetry + request updates) dominate the return leg"
    )
    # remote stats mirrors aggregate like local SchedulerStats
    assert snap["replicas"]["decode_tokens"] > 0


def test_heartbeats_carry_scheduler_stats(tiny):
    """An idle remote replica's stats mirror refreshes from heartbeats
    — the queue-delay inputs the router reads ride the envelope."""
    cm = _cluster(tiny, "loopback", replicas=2)
    cm.replicas[1].rm.stats.update({})  # forget everything
    for _ in range(3):
        cm.step()
    snap = cm.replicas[1].rm.stats.snapshot()
    assert "decode_tokens" in snap and "steps" in snap


# ---------------------------------------------------------------------------
# subprocess replica server (slow: spawns its own JAX runtime;
# premerge gate 9 runs these unfiltered)


def _spawn_server(serving_dict, index=0, seed=0):
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
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
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
def test_subprocess_server_bitwise_bare_engine(tiny):
    """True multi-process serving: a subprocess replica (its own
    single-process JAX runtime) behind the socket transport generates
    bitwise what the in-process engine generates — seeded param init on
    the pinned-threefry CPU backend is cross-process deterministic."""
    cfg, params = tiny
    rm = RequestManager(
        InferenceEngine(llama, cfg, params, ServingConfig(**sc_kwargs()))
    )
    ref = [r.output_tokens for r in rm.generate(PROMPTS, max_new_tokens=8)]
    proc, port = _spawn_server(sc_kwargs(cache_dtype="float32"))
    try:
        sc = ServingConfig(**sc_kwargs(
            replicas=1, replica_transport="socket",
            replica_endpoints=(f"127.0.0.1:{port}",),
            rpc_deadline_s=120.0,  # first RPCs pay the server's compiles
        ))
        cm = ClusterManager.build(llama, cfg, params, sc)
        got = _outputs(cm)
        assert got == ref
        cm.check_no_leaks()
        snap = cm.cluster_stats()
        assert snap["wire_bytes_sent"] > 0 and snap["rpc_errors"] == 0
        cm.replicas[0]._rpc("shutdown", {})
    finally:
        proc.terminate()
        proc.wait(timeout=30)


@pytest.mark.slow
def test_subprocess_server_survives_malformed_frames(tiny):
    """A hostile/corrupt client drops ITS connection; the server keeps
    serving the next one (and a clean transport still works)."""
    cfg, params = tiny
    proc, port = _spawn_server(sc_kwargs(cache_dtype="float32"))
    try:
        evil = socket.create_connection(("127.0.0.1", port), timeout=10)
        evil.sendall(b"garbage that is not a frame at all")
        evil.close()
        tp = SocketTransport("127.0.0.1", port)
        out = tp.call(1, "hello", {}, deadline_s=120.0)
        assert out["index"] == 0
        tp.call(2, "shutdown", {}, deadline_s=30.0)
        tp.close()
    finally:
        proc.terminate()
        proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# lock sanitizer over the threaded transport (PR-19): the deadlock
# regression — reader delivering out-of-order completions while the
# writer re-dials under the writer lock — and the sanitizer-on ==
# sanitizer-off bitwise chaos run. Gate 14 selects these by the
# `locks_sanitizer` name fragment.

from flexflow_tpu.analysis.locks import (  # noqa: E402
    active_lock_sanitizer,
    disable_lock_sanitizer,
    enable_lock_sanitizer,
)


def _out_of_order_frame_server():
    """Frame-speaking echo server that answers PAIRS of requests
    newest-first (out-of-order completion on the wire) and singles
    after a short idle — the reader-thread ordering the deadlock
    regression needs."""
    listener = socket.create_server(("127.0.0.1", 0))
    listener.settimeout(0.2)
    port = listener.getsockname()[1]
    stop = threading.Event()

    def serve_conn(conn):
        conn.settimeout(0.2)
        batch = []

        def flush():
            for r in reversed(batch):
                conn.sendall(encode_frame(
                    {"seq": r["seq"], "ok": True,
                     "result": r["args"]["x"]}
                ))
            batch.clear()

        try:
            while not stop.is_set():
                try:
                    req = read_frame_from_socket(conn)
                except DeadlineExceeded:
                    flush()
                    continue
                batch.append(req)
                if len(batch) == 2:
                    flush()
        except (TransportError, OSError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def accept_loop():
        while not stop.is_set():
            try:
                conn, _ = listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(
                target=serve_conn, args=(conn,), daemon=True
            ).start()

    threading.Thread(target=accept_loop, daemon=True).start()
    return listener, port, stop


def test_locks_sanitizer_reader_redial_deadlock_regression():
    """PR-19 satellite: the reader thread popping out-of-order
    completions under the writer lock races the caller re-dialing
    under the SAME lock after a drop. A lock-order inversion anywhere
    in that dance deadlocks two threads in production; under the
    strict sanitizer it raises LockOrderInversion here instead. Also
    proves the *_locked assert_held contracts hold on the real path."""
    import itertools
    import random

    san = enable_lock_sanitizer(strict=True)
    listener, port, stop = _out_of_order_frame_server()
    tp = SocketTransport("127.0.0.1", port, connect_timeout_s=5.0)
    rng = random.Random(7)  # seeded: same drop schedule every run
    seq = itertools.count(1)
    try:
        for _ in range(6):
            f1 = tp.call_async(next(seq), "echo", {"x": 1},
                               deadline_s=5.0)
            f2 = tp.call_async(next(seq), "echo", {"x": 2},
                               deadline_s=5.0)
            # the wire delivers f2's response FIRST (server replies
            # newest-first): the reader resolves out of issue order
            assert f2.result() == 2
            assert f1.result() == 1
            if rng.random() < 0.5:
                # writer re-dials under _lock on the next call while
                # the superseded reader generation tears down
                tp.drop_connection()
        assert san.findings == [], "\n".join(san.findings)
        assert san.acquisitions > 0
    finally:
        tp.close()
        stop.set()
        listener.close()
        disable_lock_sanitizer()


@pytest.mark.slow
def test_locks_sanitizer_chaos_bitwise(tiny):
    """The acceptance chaos plan, sanitizer-off vs
    ServingConfig(sanitizers=("locks",)): outputs, errors and fired
    faults must be BITWISE identical (the instrumented path takes no
    lock of its own around user-visible work) and the sanitizer must
    finish with zero findings over the whole fault schedule."""
    kw = dict(replicas=3, router_policy="round_robin",
              failover_retries=3)
    plan_json = FaultPlan([
        Fault("partition", replica=1, step=2, count=1000),
        Fault("delay", replica=0, step=3, count=3, seconds=0.25),
        Fault("disconnect", replica=2, step=4, count=2),
        Fault("drop", replica=0, step=5, count=3),
    ]).to_json()

    def run(sanitizers):
        cm = _cluster(tiny, "loopback", sanitizers=sanitizers, **kw)
        injector = cm.attach_faults(plan_json)
        cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS]
        for _ in range(500):
            if all(cm._terminal(c) for c in cids):
                break
            cm.step()
        cm.drain()
        outs = [cm.result(c).output_tokens for c in cids]
        errs = [cm.result(c).error for c in cids]
        fired = [(f["kind"], f["replica"], f["step"])
                 for f in injector.fired]
        return outs, errs, fired

    try:
        assert active_lock_sanitizer() is None
        base = run(())
        assert active_lock_sanitizer() is None
        sanitized = run(("locks",))
        san = active_lock_sanitizer()
        assert san is not None, "ServingConfig wiring did not enable"
        assert san.findings == [], "\n".join(san.findings)
        assert san.acquisitions > 0
        assert sanitized == base, "sanitizer changed observable behavior"
    finally:
        disable_lock_sanitizer()

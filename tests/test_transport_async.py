"""Concurrent cluster stepping tests (serve/cluster/transport.py
call-tag multiplexing + remote.py async issue/finish pairs +
manager.py fan-out drive loop + router fan-out): RpcFuture semantics,
socket out-of-order demultiplexing by call-tag, the re-dial race
(two concurrent callers on a dead link → exactly ONE reconnect),
concurrent-vs-serial loopback clusters BITWISE, the seeded
out-of-order-completion chaos run (per-replica real link delays
reorder completions; outputs/health/failover sequence bitwise the
serial arm's), the pinned one-observation-per-step guard under the
concurrent loop, and the new ClusterStats/exporter surface
(rpc_inflight_peak, cluster_step_ms + per-replica RTT percentiles).
Premerge gate 12 runs this file unfiltered; the subprocess variant is
slow-marked.
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.models import llama
from flexflow_tpu.obs.export import prometheus_text
from flexflow_tpu.serve import ClusterManager, ServingConfig
from flexflow_tpu.serve.cluster import (
    ConnectionLost,
    DeadlineExceeded,
    Fault,
    FaultPlan,
    HealthState,
    LoopbackTransport,
    RemoteError,
    Router,
    RpcFuture,
    SocketTransport,
    TransportError,
)
from flexflow_tpu.serve.cluster.transport import (
    Transport,
    encode_frame,
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


def _outputs(cm, gen=None, n_new=8, prompts=PROMPTS):
    return [
        r.output_tokens
        for r in cm.generate(prompts, gen=gen, max_new_tokens=n_new)
    ]


def _cluster(tiny, transport, **kw):
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(replica_transport=transport, **kw))
    return ClusterManager.build(llama, cfg, params, sc)


# ---------------------------------------------------------------------------
# RpcFuture + call_async units


def test_rpc_future_resolve_result_and_completion_stamp():
    fut = RpcFuture(7, "step", deadline_s=5.0)
    assert not fut.done()
    fut._resolve({"progressed": True})
    assert fut.done() and fut.completed_at is not None
    # result() is idempotent after completion
    assert fut.result() == {"progressed": True}
    assert fut.result() == {"progressed": True}


def test_rpc_future_deadline_fires_on_deadline_exactly_once():
    """A never-resolved future costs exactly its own budget, raises
    DeadlineExceeded, and fires its _on_deadline hook (the socket sync
    path's drop_connection) ONCE — a second harvest must not re-drop."""
    fut = RpcFuture(1, "step", deadline_s=0.05)
    fired = []
    fut._on_deadline = lambda: fired.append(1)
    t0 = time.perf_counter()
    with pytest.raises(DeadlineExceeded):
        fut.result()
    assert time.perf_counter() - t0 < 2.0
    assert fired == [1]
    with pytest.raises(DeadlineExceeded):
        fut.result()
    assert fired == [1], "_on_deadline re-fired on a second harvest"


def test_call_async_never_raises_transport_errors():
    """Issue-time failures come back as an already-failed future — a
    fan-out caller must be able to collect EVERY outcome at harvest."""

    class _Boom(Transport):
        def call(self, seq, method, args, deadline_s):
            raise ConnectionLost("no link")

    fut = _Boom().call_async(1, "step", {}, deadline_s=1.0)
    assert fut.done()
    with pytest.raises(ConnectionLost):
        fut.result()


def test_loopback_inline_call_async_matches_call():
    def dispatch(req):
        if req["method"] == "boom":
            return {"seq": req["seq"], "ok": False,
                    "error": {"type": "ValueError", "msg": "nope"}}
        return {"seq": req["seq"], "ok": True,
                "result": {"echo": req["args"]}}

    tp = LoopbackTransport(dispatch)
    fut = tp.call_async(1, "echo", {"x": [1, 2]}, deadline_s=1.0)
    assert fut.done(), "inline loopback must complete at issue time"
    assert fut.result() == {"echo": {"x": [1, 2]}}
    with pytest.raises(RemoteError, match="ValueError: nope"):
        tp.call_async(2, "boom", {}, deadline_s=1.0).result()


def test_loopback_threaded_worker_and_reconnect_accounting():
    """Threaded mode: completions move to the worker (with a real link
    delay) but issue-time accounting — reconnect counting included —
    stays on the caller thread in issue order."""
    def dispatch(req):
        return {"seq": req["seq"], "ok": True,
                "result": {"m": req["method"]}}

    tp = LoopbackTransport(dispatch)
    tp.threaded = True
    tp.delay_s = lambda method: 0.02 if method == "slow" else 0.0
    f_slow = tp.call_async(1, "slow", {}, deadline_s=5.0)
    f_fast = tp.call_async(2, "fast", {}, deadline_s=5.0)
    assert not f_slow.done(), "threaded issue must not block on the delay"
    assert f_slow.result() == {"m": "slow"}
    assert f_fast.result() == {"m": "fast"}
    assert f_slow.received_bytes > 0 and f_slow.sent_bytes > 0
    tp.drop_connection()
    tp.call_async(3, "fast", {}, deadline_s=5.0).result()
    assert tp.reconnects == 1
    tp.close()


# ---------------------------------------------------------------------------
# socket multiplexing: out-of-order demux + the re-dial race
# (hand-rolled frame servers — no JAX, runs in tier-1)


def _oneshot_server(handler):
    """Accept ONE connection, run ``handler(conn)``, tear down."""
    listener = socket.create_server(("127.0.0.1", 0))
    port = listener.getsockname()[1]

    def serve():
        conn, _ = listener.accept()
        try:
            conn.settimeout(10.0)
            handler(conn)
        finally:
            conn.close()
            listener.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    return port, t


def test_socket_demuxes_out_of_order_responses_by_call_tag():
    """One connection, two in-flight RPCs, responses REVERSED on the
    wire (with an unknown-tag reply thrown in): each future receives
    exactly its own tagged response; the stray tag drops on the floor."""
    def handler(conn):
        a = read_frame_from_socket(conn)
        b = read_frame_from_socket(conn)
        # a late reply to a call nobody is waiting on — must be ignored
        conn.sendall(encode_frame({"seq": 999_999, "ok": True,
                                   "result": "stray"}))
        conn.sendall(encode_frame({"seq": b["seq"], "ok": True,
                                   "result": {"who": b["method"]}}))
        conn.sendall(encode_frame({"seq": a["seq"], "ok": True,
                                   "result": {"who": a["method"]}}))

    port, t = _oneshot_server(handler)
    tp = SocketTransport("127.0.0.1", port)
    fut_a = tp.call_async(11, "alpha", {}, deadline_s=10.0)
    fut_b = tp.call_async(22, "beta", {}, deadline_s=10.0)
    # harvest in ISSUE order even though completion order is reversed
    assert fut_a.result() == {"who": "alpha"}
    assert fut_b.result() == {"who": "beta"}
    assert fut_a.received_bytes > 0 and fut_b.received_bytes > 0
    t.join(timeout=10.0)
    tp.close()


def _frame_echo_server():
    """Accept connections forever; serve each until EOF, echoing every
    request's method back under its seq."""
    listener = socket.create_server(("127.0.0.1", 0))
    listener.settimeout(0.2)
    port = listener.getsockname()[1]
    stop = threading.Event()

    def serve_conn(conn):
        conn.settimeout(10.0)
        with conn:
            while True:
                try:
                    req = read_frame_from_socket(conn)
                except TransportError:
                    return
                conn.sendall(encode_frame({
                    "seq": req["seq"], "ok": True,
                    "result": {"m": req["method"]},
                }))

    def serve():
        with listener:
            while not stop.is_set():
                try:
                    conn, _ = listener.accept()
                except socket.timeout:
                    continue
                threading.Thread(
                    target=serve_conn, args=(conn,), daemon=True
                ).start()

    threading.Thread(target=serve, daemon=True).start()
    return port, stop


def test_redial_race_single_reconnect_no_interleaved_frames():
    """Satellite bugfix pin: two callers racing onto a DEAD connection
    serialize behind the connection lock — exactly ONE re-dial is
    counted, and both calls complete (frames never interleave)."""
    port, stop = _frame_echo_server()
    try:
        tp = SocketTransport("127.0.0.1", port)
        assert tp.call(1, "warm", {}, deadline_s=10.0) == {"m": "warm"}
        assert tp.reconnects == 0
        tp.drop_connection()
        barrier = threading.Barrier(2)
        results, errors = {}, []

        def caller(seq, method):
            try:
                barrier.wait(timeout=10.0)
                results[method] = tp.call(seq, method, {},
                                          deadline_s=10.0)
            except Exception as exc:  # surfaced below
                errors.append(exc)

        threads = [
            threading.Thread(target=caller, args=(2, "left")),
            threading.Thread(target=caller, args=(3, "right")),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert not errors, errors
        assert results == {"left": {"m": "left"}, "right": {"m": "right"}}
        assert tp.reconnects == 1, (
            f"racing callers double-dialed: {tp.reconnects} reconnects"
        )
        tp.close()
    finally:
        stop.set()


def test_duplicate_seq_racing_original_executes_exactly_once():
    """At-most-once under CONCURRENT callers: a sync retry carrying
    the same seq as an in-flight threaded call must not re-execute
    the handler — dispatch serializes (core dispatch lock + the
    loopback sync path taking the global dispatch lock), the loser
    replays the seq cache. Regression: both callers used to miss the
    cache and double-execute, which double-donates engine buffers
    (deleted-array crashes mid-generation)."""
    from flexflow_tpu.serve.cluster.server import ReplicaServerCore

    calls = []
    entered = threading.Event()

    class _Rep:
        def prefix_score(self, tokens):
            calls.append(list(tokens))
            entered.set()
            time.sleep(0.05)  # hold the lock so the retry truly races
            return 42

    core = ReplicaServerCore(_Rep())
    tp = LoopbackTransport(core.dispatch)
    tp.threaded = True
    req = {"tokens": [7, 8]}
    fut = tp.call_async(11, "prefix_score", req, deadline_s=10.0)
    assert entered.wait(timeout=10.0), "threaded attempt never dispatched"
    # the "deadline-expired retry": same seq, sync path, mid-flight
    retried = tp.call(11, "prefix_score", req, deadline_s=10.0)
    original = fut.result()
    tp.close()
    assert original == {"score": 42} and retried == {"score": 42}
    assert calls == [[7, 8]], (
        f"duplicate seq re-executed the handler: {calls}"
    )


# ---------------------------------------------------------------------------
# router fan-out (satellite): issue-then-harvest in position order


class _FakeScoringReplica:
    def __init__(self, pos, score, log):
        self.pos = pos
        self.score = score
        self.log = log

    def prefix_score_async(self, tokens):
        self.log.append(("issue", self.pos))
        return ("ticket", self.pos)

    def finish_prefix_score(self, call):
        assert call == ("ticket", self.pos), "harvested someone else's call"
        self.log.append(("finish", self.pos))
        return self.score


class _FakeSyncReplica:
    def __init__(self, pos, score, log):
        self.pos = pos
        self.score = score
        self.log = log

    def prefix_score(self, tokens):
        self.log.append(("sync", self.pos))
        return self.score


def test_router_prefix_fanout_issues_all_then_harvests_in_order():
    """The prefix broadcast issues EVERY async peek before harvesting
    any (one round-trip, not N), mixes sync replicas transparently, and
    the scored list is identical to the serial broadcast's."""
    log = []
    reps = [
        _FakeScoringReplica(0, 5, log),
        _FakeSyncReplica(1, 9, log),
        _FakeScoringReplica(2, 3, log),
    ]
    router = Router(reps, "prefix")
    scored = router._prefix_scores([1, 2, 3, 4], [0, 1, 2])
    assert scored == [(5, 0), (9, 1), (3, 2)]
    issues = [e for e in log if e[0] == "issue"]
    finishes = [e for e in log if e[0] != "issue"]
    assert issues == [("issue", 0), ("issue", 2)]
    assert finishes == [("finish", 0), ("sync", 1), ("finish", 2)]
    assert log.index(("issue", 2)) < log.index(("finish", 0)), (
        "router harvested before finishing the issue fan-out"
    )


# ---------------------------------------------------------------------------
# concurrent drive loop == serial drive loop, bitwise


def test_concurrent_stepping_bitwise_serial_with_reordered_completions(tiny):
    """The tentpole contract: the fan-out loop over threaded loopback
    links with INVERTED per-replica delays (replica 0 slowest → every
    step completes in reverse issue order) produces bitwise the serial
    loop's outputs, and the new depth/latency telemetry registers."""
    kw = dict(replicas=3, router_policy="round_robin")
    ref = _outputs(_cluster(tiny, "loopback",
                            concurrent_stepping=False, **kw))
    cm = _cluster(tiny, "loopback", **kw)
    for pos, rep in enumerate(cm.replicas):
        rep.transport.threaded = True
        rep.transport.delay_s = 0.006 - 0.002 * pos
    got = _outputs(cm)
    assert got == ref, "concurrent stepping diverged from the serial loop"
    st = cm.cluster_stats()
    assert st["rpc_errors"] == 0
    assert st["rpc_inflight_peak"] >= 2, "step RPCs never overlapped"
    assert st["cluster_step_ms_p50"] > 0
    assert st["rpc_rtt_ms_p50"] > 0
    cm.check_no_leaks()
    for rep in cm.replicas:
        rep.close()


def test_concurrent_chaos_out_of_order_completions_bitwise(tiny):
    """Satellite acceptance chaos: partition + disconnect + drop over
    3 threaded-loopback replicas whose real link delays reorder every
    step's completions — outputs, terminal errors, health transitions
    and the fired fault sequence are BITWISE the serial drive loop's
    (and a re-run of the concurrent arm reproduces itself exactly)."""
    kw = dict(replicas=3, router_policy="round_robin",
              failover_retries=3)
    ref = _outputs(_cluster(tiny, "loopback",
                            concurrent_stepping=False, **kw))
    plan_json = FaultPlan([
        Fault("partition", replica=1, step=3, count=1000),
        Fault("disconnect", replica=2, step=4, count=2),
        Fault("drop", replica=0, step=5, count=3),
    ]).to_json()
    delays = (0.002, 0.006, 0.004)

    def run(concurrent):
        cm = _cluster(tiny, "loopback",
                      concurrent_stepping=concurrent, **kw)
        for pos, rep in enumerate(cm.replicas):
            rep.transport.threaded = True
            rep.transport.delay_s = delays[pos]
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
        health = cm.health_snapshot()
        fired = [(f["kind"], f["replica"], f["step"])
                 for f in injector.fired]
        st = cm.cluster_stats()
        cm.check_no_leaks()  # survivors only — DOWN pool excluded
        for pos, rep in enumerate(cm.replicas):
            if cm.health[pos].state is not HealthState.DOWN:
                assert rep.rm.hold_finished == set()
        for rep in cm.replicas:
            rep.close()
        return outs, errs, health, fired, st

    outs_a, errs_a, health_a, fired_a, st_a = run(True)
    outs_b, errs_b, health_b, fired_b, _ = run(True)
    assert (outs_a, errs_a, health_a, fired_a) == (
        outs_b, errs_b, health_b, fired_b
    ), "seeded concurrent chaos diverged between runs"
    outs_s, errs_s, health_s, fired_s, st_s = run(False)
    assert outs_a == outs_s == ref, (
        "completion order changed cluster outputs"
    )
    assert errs_a == errs_s == [None] * len(PROMPTS)
    assert health_a == health_s, (
        f"health transitions diverged: {health_a} vs {health_s}"
    )
    # the GLOBAL interleaving of per-replica fault consults legitimately
    # differs (the fan-out issues every attempt 0 before any retries;
    # the serial loop drains one replica's retries before the next) —
    # what must hold is each replica's OWN firing sequence
    def _per_replica(fired):
        return {
            r: [f for f in fired if f[1] == r] for r in range(3)
        }

    assert _per_replica(fired_a) == _per_replica(fired_s), (
        "per-replica fault firing sequence diverged"
    )
    for key in ("replica_down", "failovers", "reconnects", "rpc_errors"):
        assert st_a[key] == st_s[key], (
            f"{key}: concurrent {st_a[key]} != serial {st_s[key]}"
        )
    assert st_a["rpc_inflight_peak"] >= 2


@pytest.mark.parametrize("concurrent", [True, False])
def test_one_observation_per_step_guard_pinned(tiny, concurrent):
    """Pinned regression (satellite): a replica simultaneously inside a
    heartbeat gap AND failing its step RPC gets ONE health observation
    per cluster step under BOTH drive loops — failure_threshold=2 must
    take exactly two cluster steps to trip, never one."""
    cm = _cluster(tiny, "loopback", replicas=2, heartbeat_gap_steps=1,
                  concurrent_stepping=concurrent)
    cm.attach_faults(FaultPlan([
        Fault("partition", replica=1, step=1, count=1000),
    ]))
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
    cids = list(cm.requests)
    for _ in range(200):
        if all(cm._terminal(c) for c in cids):
            break
        cm.step()
    assert all(cm._terminal(c) for c in cids)


@pytest.mark.parametrize("concurrent", [True, False])
def test_heartbeat_gap_arithmetic_pinned_under_both_loops(tiny, concurrent):
    """Gap detection stays counted in deterministic CLUSTER steps under
    the concurrent loop: identical down-at arithmetic in both arms."""
    cm = _cluster(tiny, "loopback", replicas=2, heartbeat_gap_steps=3,
                  concurrent_stepping=concurrent)
    rep = cm.replicas[1]

    def dead_dispatch(request):
        raise ConnectionLost("link down")

    rep.transport.dispatch = dead_dispatch
    down_at = None
    for step in range(1, 12):
        cm.step()
        if cm.health[1].state is HealthState.DOWN and down_at is None:
            down_at = step
    assert down_at == 4, f"gap arithmetic drifted (down at {down_at})"
    assert cm.stats.heartbeat_gaps >= 2
    assert cm.health_snapshot()[0] == "healthy"


# ---------------------------------------------------------------------------
# telemetry: in-flight depth, step/RTT percentiles, exporter rendering


def test_cluster_stats_async_fields_and_exporter(tiny):
    cm = _cluster(tiny, "loopback", replicas=2,
                  router_policy="round_robin")
    _outputs(cm, n_new=4)
    snap = cm.cluster_stats()
    for key in ("rpc_inflight_peak", "cluster_step_ms_p50",
                "cluster_step_ms_p99", "rpc_rtt_ms_p50",
                "rpc_rtt_ms_p99", "rpc_rtt_ms_per_replica"):
        assert key in snap, key
    assert snap["rpc_inflight_peak"] >= 2
    assert snap["cluster_step_ms_p99"] >= snap["cluster_step_ms_p50"] > 0
    per_rep = snap["rpc_rtt_ms_per_replica"]
    assert set(per_rep) == {0, 1}
    for pcts in per_rep.values():
        assert pcts["p99"] >= pcts["p50"] >= 0
    text = prometheus_text(cluster=cm.stats)
    assert "flexflow_cluster_rpc_inflight_peak" in text
    assert "flexflow_cluster_cluster_step_ms_p50" in text
    assert 'flexflow_cluster_rpc_rtt_ms{quantile="p50",replica="0"}' in text
    assert 'flexflow_cluster_rpc_rtt_ms{quantile="p99",replica="1"}' in text


# ---------------------------------------------------------------------------
# subprocess replica servers under the concurrent loop (slow: spawns
# its own JAX runtimes; premerge gate 12 runs this unfiltered)


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
def test_subprocess_concurrent_cluster_bitwise_serial(tiny):
    """True multi-process fan-out: two subprocess replica servers
    stepped concurrently over real sockets generate bitwise what the
    serial loopback cluster generates, with overlapped step RPCs."""
    cfg, params = tiny
    ref = _outputs(_cluster(tiny, "loopback", replicas=2,
                            router_policy="round_robin",
                            concurrent_stepping=False))
    procs = []
    try:
        ports = []
        for i in range(2):
            proc, port = _spawn_server(
                sc_kwargs(cache_dtype="float32"), index=i
            )
            procs.append(proc)
            ports.append(port)
        sc = ServingConfig(**sc_kwargs(
            replicas=2, replica_transport="socket",
            replica_endpoints=tuple(
                f"127.0.0.1:{p}" for p in ports
            ),
            router_policy="round_robin",
            rpc_deadline_s=120.0,  # first RPCs pay the server's compiles
        ))
        cm = ClusterManager.build(llama, cfg, params, sc)
        got = _outputs(cm)
        assert got == ref, "socket fan-out diverged from serial loopback"
        cm.check_no_leaks()
        snap = cm.cluster_stats()
        assert snap["rpc_errors"] == 0
        assert snap["rpc_inflight_peak"] >= 2, (
            "subprocess step RPCs never overlapped"
        )
        assert snap["rpc_rtt_ms_p50"] > 0
        for rep in cm.replicas:
            rep._rpc("shutdown", {})
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# PR-19 satellite: ClusterStats wire/RPC counters are read-modify-write
# from the worker/reader thread (received bytes) and caller threads
# (sent bytes, retries/errors) CONCURRENTLY — every increment must land
# under _STATS_LOCK, so the totals are exact, not approximate.


def test_wire_counter_atomicity_under_thread_hammer():
    """8 threads x 2000 bare increments: any unlocked += on the shared
    ClusterStats would lose updates and land below the exact total."""
    from flexflow_tpu.metrics import ClusterStats

    st = ClusterStats()
    tp = Transport(stats=st)
    threads = [
        threading.Thread(
            target=lambda: [tp._count(sent=1, received=2)
                            for _ in range(2000)]
        )
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tp.bytes_sent == 8 * 2000
    assert tp.bytes_received == 2 * 8 * 2000
    assert st.wire_bytes_sent == 8 * 2000
    assert st.wire_bytes_received == 2 * 8 * 2000


def test_wire_counter_accuracy_concurrent_async_steps():
    """Threaded loopback under concurrent issue/harvest: the transport
    and ClusterStats wire totals must equal the EXACT sum of per-frame
    byte counts the futures observed — worker-thread received-side
    increments interleaving with caller-thread sent-side increments."""
    from flexflow_tpu.metrics import ClusterStats

    st = ClusterStats()

    def dispatch(req):
        return {"seq": req["seq"], "ok": True, "result": req["args"]}

    tp = LoopbackTransport(dispatch, stats=st)
    tp.threaded = True
    futs = [
        tp.call_async(seq, "echo", {"x": list(range(seq % 7))},
                      deadline_s=10.0)
        for seq in range(1, 101)
    ]
    for fut in futs:
        fut.result()
    sent = sum(f.sent_bytes for f in futs)
    received = sum(f.received_bytes for f in futs)
    assert sent > 0 and received > 0
    assert (tp.bytes_sent, tp.bytes_received) == (sent, received)
    assert (st.wire_bytes_sent, st.wire_bytes_received) == (sent, received)
    tp.close()

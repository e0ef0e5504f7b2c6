"""Fused transformer-stack op + memory-aware search tests.

Covers VERDICT r3 items #2 (the Unity search must reach the fast
scan+remat+flash path via ops/fused_transformer) and #3 (memory-aware
search: HBM accounting + the λ tradeoff sweep, reference
``graph.cc:2132-2190`` perform_memory_search)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import flexflow_tpu as ff
from flexflow_tpu.bench_search import build_searched_lm
from flexflow_tpu.core.mesh import MachineSpec, set_mesh as _set_mesh
from flexflow_tpu.models import llama, transformer
from flexflow_tpu.optimizers import AdamOptimizer, SGDOptimizer
from flexflow_tpu.search import CostModel, TPUChip, TPUTopology, optimize
from flexflow_tpu.search.unity import memory_search
from flexflow_tpu.ops import get_op


V, D, F, L, H = 64, 32, 64, 2, 4
B, S = 2, 16


def _lm(num_devices=1, batch=B):
    return build_searched_lm(
        vocab_size=V, hidden_size=D, intermediate_size=F, num_layers=L,
        num_heads=H, batch=batch, seq=S, dtype=jnp.float32,
        config=ff.FFConfig(batch_size=batch, num_devices=num_devices,
                           search_budget=4),
    )


def test_fused_stack_matches_llama_forward():
    """The op must compute exactly what the decoder's scanned blocks
    compute (same weight layout, same RoPE/mask conventions)."""
    cfg = llama.LLaMAConfig(
        vocab_size=V, hidden_size=D, intermediate_size=F,
        num_hidden_layers=L, num_attention_heads=H, num_key_value_heads=H,
        max_position_embeddings=S, dtype=jnp.float32,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)

    op = get_op("transformer_decoder_stack")
    attrs = dict(num_layers=L, num_heads=H, num_kv_heads=H,
                 intermediate_size=F, eps=cfg.norm_eps,
                 rope_theta=cfg.rope_theta, remat=False, attention="xla")
    from flexflow_tpu.ops.registry import OpContext

    (got,) = op.forward(params["layers"], [x], attrs, OpContext(training=False))

    rope = transformer.rope_freqs(cfg, jnp.arange(S, dtype=jnp.int32))
    mask = jnp.tril(jnp.ones((S, S), bool))

    def body(carry, p_l):
        y, _ = transformer.block(cfg, p_l, carry, rope, None, mask)
        return y, None

    want, _ = jax.lax.scan(body, x, params["layers"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_fused_stack_remat_same_grads():
    """remat=True must change memory, not math: same loss and same
    gradients as remat=False."""
    op = get_op("transformer_decoder_stack")
    from flexflow_tpu.core.tensor import TensorSpec
    from flexflow_tpu.ops.registry import OpContext

    spec = TensorSpec((B, S, D), "float32")
    base = dict(num_layers=L, num_heads=H, num_kv_heads=None,
                intermediate_size=F, eps=1e-6, rope_theta=10000.0,
                attention="xla")
    w = op.init(jax.random.PRNGKey(0), [spec], dict(base, remat=False))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)

    def loss(w, x, remat):
        (y,) = op.forward(w, [x], dict(base, remat=remat),
                          OpContext(training=True))
        return (y.astype(jnp.float32) ** 2).mean()

    l0, g0 = jax.value_and_grad(loss)(w, x, False)
    l1, g1 = jax.value_and_grad(loss)(w, x, True)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for k in g0:
        np.testing.assert_allclose(
            np.asarray(g0[k]), np.asarray(g1[k]), rtol=1e-4, atol=1e-6
        )


def test_searched_compile_runs_and_learns():
    """compile(auto_parallel=True) over embed→fused-stack→head executes
    and takes optimizer steps (loss decreases on a tiny overfit task)."""
    m = _lm()
    m.compile(
        optimizer=AdamOptimizer(lr=5e-3),
        loss_type="sparse_categorical_crossentropy",
        metrics=(),
        auto_parallel=True,
    )
    assert m._search_report is not None
    rng = np.random.default_rng(0)
    data = rng.integers(0, V, size=(B, S + 1)).astype(np.int32)
    x, y = {"tokens": data[:, :-1]}, data[:, 1:]
    losses = []
    with _set_mesh(m.mesh):
        batch = m._shard_batch(x)
        yb = m._shard_batch({"y": y})["y"]
        params, opt, st = m.params, m.opt_state, m.model_state
        for i in range(30):
            params, opt, st, loss, _ = m._train_step(
                params, opt, st, jax.random.PRNGKey(i), batch, yb
            )
            losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[::10]


def test_searched_tp_megatron_matches_single_device():
    """On the 8-device mesh the search (budget permitting) may pick
    TP_MEGATRON for the fused stack; whatever it picks, the compiled
    loss must match the 1-device compile bit-for-bit-ish."""
    losses = {}
    for ndev in (1, 8):
        m = _lm(num_devices=ndev, batch=8)
        m.compile(
            optimizer=SGDOptimizer(lr=0.0),
            loss_type="sparse_categorical_crossentropy",
            metrics=(),
            auto_parallel=True,
        )
        rng = np.random.default_rng(1)
        data = rng.integers(0, V, size=(8, S + 1)).astype(np.int32)
        with _set_mesh(m.mesh):
            batch = m._shard_batch({"tokens": data[:, :-1]})
            yb = m._shard_batch({"y": data[:, 1:]})["y"]
            *_, loss, _m = m._train_step(
                m.params, m.opt_state, m.model_state,
                jax.random.PRNGKey(0), batch, yb,
            )
            losses[ndev] = float(loss)
    assert losses[1] == pytest.approx(losses[8], rel=2e-4)


def test_tp_megatron_state_offered_and_priced():
    m = _lm(num_devices=8, batch=8)
    topo = TPUTopology(chip=TPUChip.v5e(), num_chips=8)
    cm = CostModel(topo=topo, machine=MachineSpec(data=2, model=4))
    stack = next(
        n for n in m.graph.nodes if n.op_type == "transformer_decoder_stack"
    )
    from flexflow_tpu.search.simulator import candidate_states

    states = candidate_states(stack, cm.machine)
    assert "TP_MEGATRON" in states
    # Megatron pricing = compute/(dp*tp) + the internal per-layer
    # all-reduces (for this tiny model the collective latency dominates
    # — exactly why a correct search would keep it unsharded).
    rep = cm.op_cost(m.graph, stack, "REP")
    comm = cm._internal_comm_cost(
        stack, [m.graph.out_spec(stack.inputs[0])], "TP_MEGATRON"
    )
    tp = cm.op_cost(m.graph, stack, "TP_MEGATRON")
    assert comm > 0
    assert tp == pytest.approx(rep / 8 + comm, rel=0.5)


# ---------------------------------------------------------------------------
# memory-aware search (VERDICT #3)


def _fat_mlp(num_devices=4):
    """Two fat dense layers whose replicated weights blow a small HBM
    budget, but whose TP-sharded weights fit."""
    cfg = ff.FFConfig(batch_size=8, num_devices=num_devices, search_budget=2)
    m = ff.FFModel(cfg)
    t = m.create_tensor((8, 1024), name="x")
    t = m.dense(t, 4096, name="up")
    t = m.dense(t, 1024, name="down")
    return m


def test_memory_search_rejects_oom_strategy():
    g = _fat_mlp().graph
    topo = TPUTopology(chip=TPUChip.v5e(), num_chips=4)
    # parameter-parallel disabled: the ONLY memory lever on a data-only
    # machine is gone, so infeasibility must be detected
    cm = CostModel(topo=topo, machine=MachineSpec(data=4, model=1),
                   enable_parameter=False)
    cm_tp = CostModel(topo=topo, machine=MachineSpec(data=1, model=4))

    # weights: 2 * (1024*4096*4B) * (1+opt) ≈ 134 MB replicated
    from flexflow_tpu.search.placement import placement_dp

    unconstrained = placement_dp(g, cm)
    full = cm.strategy_memory_bytes(g, unconstrained)
    budget = full * 0.5  # DP cannot fit; TP (weights/4) can

    # pure-DP machine without parameter-parallel: even λ=1 can't shard
    # weights → infeasible
    s_dp, lam_dp = memory_search(g, cm, budget)
    assert cm.strategy_memory_bytes(g, s_dp) > budget

    # same machine WITH parameter-parallel: the λ sweep finds a fitting
    # ZeRO-style strategy (weights/grads/opt shard over the data axis)
    cm_zero = CostModel(topo=topo, machine=MachineSpec(data=4, model=1))
    s_zero, _ = memory_search(g, cm_zero, budget)
    assert cm_zero.strategy_memory_bytes(g, s_zero) <= budget
    assert any(s == "PARAM" for s in s_zero.choices.values())

    # TP machine: the λ sweep finds a fitting strategy
    s_tp, lam_tp = memory_search(g, cm_tp, budget)
    assert cm_tp.strategy_memory_bytes(g, s_tp) <= budget
    assert any(s in ("TP_COL", "TP_ROW") for s in s_tp.choices.values())

    # end-to-end: optimize() must pick a feasible machine under the
    # budget, and reports the footprint
    g2, strat, report = optimize(
        g, 4, topo, training=True, budget=2, memory_budget=budget
    )
    assert report.memory_feasible
    assert report.memory_bytes <= budget
    # ...and with the budget lifted it keeps the fastest (possibly
    # memory-hungrier) strategy instead
    _, _, report_inf = optimize(
        g, 4, topo, training=True, budget=2, memory_budget=float("inf")
    )
    assert report_inf.memory_feasible


def test_fused_stack_activation_bytes_reflect_remat():
    op = get_op("transformer_decoder_stack")
    from flexflow_tpu.core.tensor import TensorSpec

    spec = TensorSpec((B, S, D), "float32")
    base = dict(num_layers=L, num_heads=H, num_kv_heads=None,
                intermediate_size=F, eps=1e-6, rope_theta=10000.0,
                attention="xla")
    with_remat = op.activation_bytes([spec], dict(base, remat=True), True)
    without = op.activation_bytes([spec], dict(base, remat=False), True)
    assert with_remat < without
    assert op.activation_bytes([spec], dict(base, remat=True), False) < with_remat


def test_param_state_executes_and_matches_dp():
    """PARAM (ZeRO-style weight sharding over the data axis) must
    execute via GSPMD and produce the same loss as plain DP (reference
    enable_parameter_parallel, config.h:160-162)."""
    import flexflow_tpu.search as search

    def build():
        cfg = ff.FFConfig(batch_size=8, num_devices=8)
        m = ff.FFModel(cfg)
        t = m.create_tensor((8, 16), name="x")
        t = m.dense(t, 32, activation="relu", name="d0")
        t = m.dense(t, 4, name="d1")
        m.softmax(t, name="sm")
        return m

    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 16)).astype(np.float32)
    y = rng.integers(0, 4, size=8).astype(np.int32)

    losses = {}
    for states in ("DP", "PARAM"):
        m = build()
        machine = MachineSpec(data=8, model=1)
        strat = search.ParallelStrategy(
            machine=machine,
            choices={
                n.id: (states if n.op_type == "dense" else "DP")
                for n in m.graph.nodes
            },
        )
        strat.stamp(m.graph)
        m._strategy = strat
        m._param_pspecs = strat.weight_pspecs(m.graph)
        m.config.data_parallelism_degree = 8
        m.compile(optimizer=SGDOptimizer(lr=0.0), metrics=())
        with _set_mesh(m.mesh):
            batch = m._shard_batch({"x": x})
            yb = m._shard_batch({"y": y})["y"]
            *_, loss, _mv = m._train_step(
                m.params, m.opt_state, m.model_state,
                jax.random.PRNGKey(0), batch, yb,
            )
            losses[states] = float(loss)
        if states == "PARAM":
            # the kernels really are sharded over the data axis
            k = m.params["d0"]["kernel"]
            assert "data" in str(k.sharding.spec)
    assert losses["PARAM"] == pytest.approx(losses["DP"], rel=1e-5)


def test_param_state_embedding_matches_dp():
    """PARAM on an embedding table (rows sharded over data) must equal
    the DP loss — the second op family that implements tp_shard='param'."""
    import flexflow_tpu.search as search

    def build():
        cfg = ff.FFConfig(batch_size=8, num_devices=8)
        m = ff.FFModel(cfg)
        t = m.create_tensor((8, 4), dtype="int32", name="ids")
        t = m.embedding(t, 64, 16, aggr="sum", name="emb")
        t = m.dense(t, 4, name="head")
        m.softmax(t, name="sm")
        return m

    rng = np.random.default_rng(2)
    x = rng.integers(0, 64, size=(8, 4)).astype(np.int32)
    y = rng.integers(0, 4, size=8).astype(np.int32)
    losses = {}
    for state in ("DP", "PARAM"):
        m = build()
        machine = MachineSpec(data=8, model=1)
        strat = search.ParallelStrategy(
            machine=machine,
            choices={
                n.id: (state if n.op_type == "embedding" else "DP")
                for n in m.graph.nodes
            },
        )
        strat.stamp(m.graph)
        m._strategy = strat
        m._param_pspecs = strat.weight_pspecs(m.graph)
        m.config.data_parallelism_degree = 8
        m.compile(optimizer=SGDOptimizer(lr=0.0), metrics=())
        with _set_mesh(m.mesh):
            batch = m._shard_batch({"ids": x})
            yb = m._shard_batch({"y": y})["y"]
            *_, loss, _mv = m._train_step(
                m.params, m.opt_state, m.model_state,
                jax.random.PRNGKey(0), batch, yb,
            )
            losses[state] = float(loss)
        if state == "PARAM":
            assert "data" in str(m.params["emb"]["table"].sharding.spec)
    assert losses["PARAM"] == pytest.approx(losses["DP"], rel=1e-5)

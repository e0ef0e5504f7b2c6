"""MoE tests — routing vs a naive per-token loop, group_by/aggregate
composition vs the fused op, load-balance loss, expert-parallel compile,
and end-to-end training (the reference's MoE example,
examples/cpp/mixture_of_experts/moe.cc, as a blob-classification fit)."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.mesh import MachineSpec
from flexflow_tpu.ops.moe import _capacity, _routing
from flexflow_tpu.ops.registry import OpContext, get_op


def test_routing_matches_naive_loop():
    """Dense one-hot dispatch must equal the obvious per-token queue
    simulation (the reference's scatter kernel semantics)."""
    rng = np.random.default_rng(0)
    N, E, K, C = 12, 4, 2, 5
    probs = jax.nn.softmax(jnp.asarray(rng.normal(size=(N, E)), jnp.float32))
    dispatch, combine, gates, idx = _routing(probs, K, C)
    dispatch, combine = np.asarray(dispatch), np.asarray(combine)
    idx, gates = np.asarray(idx), np.asarray(gates)

    # naive queue simulation: k-major then token order (matches the
    # cumsum over the flattened (K, N) axis)
    counts = np.zeros(E, int)
    expect = np.zeros((N, E, C))
    assigned = {}
    for k in range(K):
        for n in range(N):
            e = idx[n, k]
            if counts[e] < C:
                expect[n, e, counts[e]] = 1.0
                assigned[(n, k)] = (e, counts[e])
                counts[e] += 1
    np.testing.assert_allclose(dispatch, expect, atol=1e-6)
    for (n, k), (e, c) in assigned.items():
        np.testing.assert_allclose(combine[n, e, c], gates[n, k], rtol=1e-5)


def test_group_by_aggregate_composition_matches_moe():
    """top_k → group_by → expert FFN → aggregate must equal the fused
    moe op with the same weights (reference training-vs-fused parity)."""
    rng = np.random.default_rng(1)
    N, D, E, K, F = 16, 8, 4, 2, 16
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)

    cfg = ff.FFConfig(batch_size=N, num_devices=1)
    m = ff.FFModel(cfg)
    t = m.create_tensor((N, D), name="x")
    y = m.moe(t, num_experts=E, top_k=K, expert_hidden=F,
              load_balance_lambda=0.0, name="moe0")
    params = m.init_params(jax.random.PRNGKey(5))
    fused, _ = m.run_graph(params, {"x": x}, training=False)

    # manual composition with the same weights
    w = params["moe0"]
    probs = jax.nn.softmax(
        jnp.matmul(x, w["gate"], preferred_element_type=jnp.float32), -1
    ).astype(x.dtype)
    gb = get_op("group_by")
    ag = get_op("aggregate")
    ctx = OpContext(training=False)
    C = _capacity(N, E, K, 1.25)
    buckets, dispatch, combine = gb.forward(
        {}, [x, probs], {"k": K, "capacity_factor": 1.25}, ctx
    )
    from flexflow_tpu.ops.moe import _expert_ffn

    out = _expert_ffn(buckets, w, "relu")
    (y2,) = ag.forward({}, [out, combine, probs], {}, ctx)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(y2), atol=1e-5)


def test_moe_aux_loss_collected_in_training():
    cfg = ff.FFConfig(batch_size=8, num_devices=1)
    m = ff.FFModel(cfg)
    t = m.create_tensor((8, 8), name="x")
    t = m.moe(t, num_experts=4, top_k=2, expert_hidden=16,
              load_balance_lambda=0.01)
    params = m.init_params(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(8, 8)), jnp.float32)
    _, st = m.run_graph(params, {"x": x}, training=True,
                        rng=jax.random.PRNGKey(0))
    assert "__aux__" in st and len(st["__aux__"]) == 1
    aux = float(st["__aux__"][0])
    assert aux > 0.0  # load-balance loss ≥ λ·1.0 at perfect balance


def test_moe_trains_e2e():
    rng = np.random.default_rng(0)
    x = (rng.normal(size=(128, 16)) + np.repeat(np.eye(4, 16) * 4, 32, 0)).astype(
        np.float32
    )
    y = np.repeat(np.arange(4), 32).astype(np.int32)
    cfg = ff.FFConfig(batch_size=32, epochs=6, num_devices=1)
    m = ff.FFModel(cfg)
    t = m.create_tensor((32, 16), name="x")
    t = m.moe(t, num_experts=4, top_k=2, expert_hidden=32)
    t = m.dense(t, 4)
    t = m.softmax(t)
    m.compile(optimizer=ff.AdamOptimizer(lr=0.01))
    perf = m.fit(x, y)
    assert perf.averages()["accuracy"] > 0.8


def test_expert_parallel_compile_8dev():
    """EP: expert dim sharded over the expert mesh axis; the jitted step
    must compile and run on the virtual 8-device mesh (expert=4, data=2)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = rng.integers(0, 4, size=(64,)).astype(np.int32)
    cfg = ff.FFConfig(batch_size=32, epochs=1, num_devices=8,
                      expert_parallelism_degree=4)
    m = ff.FFModel(cfg)
    t = m.create_tensor((32, 16), name="x")
    t = m.moe(t, num_experts=4, top_k=2, expert_hidden=32, name="moe_ep")
    t = m.dense(t, 4)
    t = m.softmax(t)
    m.compile(optimizer=ff.SGDOptimizer(lr=0.05))
    # expert weights must actually shard over the expert axis
    w1 = m.params["moe_ep"]["w1"]
    assert "expert" in str(w1.sharding.spec)
    m.fit(x, y)


def test_experts_op_inference():
    """Fused experts on precomputed routing ≈ moe's expert path."""
    rng = np.random.default_rng(3)
    N, D, E, K, F = 8, 8, 4, 2, 16
    cfg = ff.FFConfig(batch_size=N, num_devices=1)
    m = ff.FFModel(cfg)
    x_t = m.create_tensor((N, D), name="x")
    g_t = m.create_tensor((N, E), name="gate_logits")
    probs = m.softmax(g_t, axis=-1)
    vals = m.top_k(probs, K, name="router")
    y = m.experts(x_t, vals[1], vals[0], num_experts=E, top_k=K,
                  expert_hidden=F, capacity_factor=2.0)
    params = m.init_params(jax.random.PRNGKey(7))
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    gl = jnp.asarray(rng.normal(size=(N, E)), jnp.float32)
    out, _ = m.run_graph(params, {"x": x, "gate_logits": gl}, training=False)
    assert np.asarray(out).shape == (N, D)
    assert np.isfinite(np.asarray(out)).all()


def test_aggregate_spec_fixed_routing():
    """aggregate_spec matches aggregate's forward but carries no combine
    gradient and no aux loss (reference ops/aggregate_spec.h:14)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import get_op
    from flexflow_tpu.ops.registry import OpContext

    E, C, D, N = 2, 3, 4, 5
    key = jax.random.PRNGKey(0)
    eo = jax.random.normal(key, (E, C, D), jnp.float32)
    combine = jax.nn.softmax(
        jax.random.normal(jax.random.fold_in(key, 1), (N, E, C)), axis=-1
    )
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.fold_in(key, 2), (N, E)), axis=-1
    )
    spec_op, agg_op = get_op("aggregate_spec"), get_op("aggregate")
    ctx = OpContext(training=True, state_updates={})
    (y_spec,) = spec_op.forward(None, [eo, combine, probs], {}, ctx)
    (y_agg,) = agg_op.forward(
        None, [eo, combine, probs], {"load_balance_lambda": 0.0}, ctx
    )
    np.testing.assert_allclose(np.asarray(y_spec), np.asarray(y_agg), rtol=1e-6)

    def loss(combine):
        ctx2 = OpContext(training=True, state_updates={})
        (y,) = spec_op.forward(None, [eo, combine, probs], {}, ctx2)
        return (y ** 2).sum()

    g = jax.grad(loss)(combine)
    assert float(jnp.abs(g).max()) == 0.0  # routing is fixed in spec mode


def test_cache_op_serves_cached_value_at_inference():
    """cache op: training records the activation into model state;
    inference returns the cached copy (reference ops/cache.h:8)."""
    import numpy as _np

    cfg = ff.FFConfig(batch_size=4, num_devices=1)
    m = ff.FFModel(cfg)
    t = m.create_tensor((4, 8), name="x")
    t = m.dense(t, 8, name="enc")
    t = m.cache(t, name="memo")
    t = m.dense(t, 2, name="head")
    m.compile(optimizer=ff.SGDOptimizer(lr=0.0), metrics=())
    x1 = _np.random.default_rng(0).normal(size=(4, 8)).astype(_np.float32)
    x2 = _np.random.default_rng(1).normal(size=(4, 8)).astype(_np.float32)
    y = _np.zeros(4, _np.int32)
    m.fit(x1, y, batch_size=4, epochs=1, shuffle=False, verbose=False)
    out_cached = _np.asarray(m.forward(x2))   # should use x1's cached enc
    out_ref = _np.asarray(m.forward(x1))
    _np.testing.assert_allclose(out_cached, out_ref, rtol=1e-5)


# ---------------------------------------------------------------------------
# The generic decoder's sparse layer with its tokens ROUTED (ISSUE 36): the
# paged serving step of ``mixtral`` / ``qwen2_moe`` against the all-expert
# einsum (``transformer._moe_ffn``) on the same weights.

from flexflow_tpu.models import mixtral, qwen2_moe, transformer  # noqa: E402
from flexflow_tpu.serve import kernels as serve_kernels  # noqa: E402

SLOTS, CHUNK, PAGE, PAGES = 4, 16, 8, 4
CACHE_LEN = PAGE * PAGES - 1     # the scratch position: a slot's last line


def _sparse_family(name):
    """mixtral: softmax over the chosen k; qwen2_moe: softmax over all
    experts, the chosen weights verbatim, and a shared expert."""
    mod = {"mixtral": mixtral, "qwen2_moe": qwen2_moe}[name]
    cfg = mod.tiny(dtype=jnp.float32)
    assert cfg.moe_norm_topk == (name == "mixtral")
    assert bool(cfg.moe_shared_expert_intermediate_size) == (name == "qwen2_moe")
    return mod, cfg, mod.init_params(jax.random.PRNGKey(3), cfg)


def _paged_step(mod, cfg, params, cache, tokens, q_len, first, **kw):
    """One paged step over rows whose real tokens are their leading
    ``q_len`` columns from position ``first``; the rest is padding at
    the scratch position."""
    C = tokens.shape[1]
    cols = np.arange(C)[None]
    positions = np.where(cols < q_len[:, None], first[:, None] + cols,
                         CACHE_LEN).astype(np.int32)
    table = jnp.arange(SLOTS * PAGES, dtype=jnp.int32).reshape(SLOTS, PAGES)
    return mod.serve_step_paged(
        params, cache, jnp.asarray(tokens), jnp.asarray(positions),
        jnp.asarray(np.maximum(q_len - 1, 0), jnp.int32), None, None, table,
        cfg=cfg, cache_len=CACHE_LEN, **kw)


@pytest.mark.parametrize("program", ["padded", "rung", "decode"])
@pytest.mark.parametrize("kernels", ["xla", "pallas"])
@pytest.mark.parametrize("family", ["mixtral", "qwen2_moe"])
def test_routed_paged_step_matches_the_all_expert_einsum(
        family, kernels, program, monkeypatch):
    """The padded step and a packed rung, each with padding places and
    a row that holds no real token: the logits of the rows that sample
    and the K/V lines written are the einsum step's within float32
    accumulation, and each layer's tokens per expert sum to real tokens
    x k. The C=1 step (8 pairs over 4 experts: under a row tile each)
    takes the einsum itself and returns zeros."""
    mod, cfg, params = _sparse_family(family)
    rng = np.random.default_rng(7)
    cache = mod.init_paged_kv_cache(cfg, SLOTS * PAGES, PAGE, jnp.float32)
    q_len, first = np.array([16, 3, 0, 9]), np.zeros(SLOTS, np.int64)
    tokens = rng.integers(0, cfg.vocab_size, (SLOTS, CHUNK)).astype(np.int32)
    kw = dict(kernels=kernels)
    if program == "rung":
        kw["pack"] = 32
    if program == "decode":   # from a cache that holds the prompts
        _, cache = _paged_step(mod, cfg, params, cache, tokens, q_len, first,
                               **kw)
        cache.pop("moe_counts")
        first, q_len = q_len, np.array([1, 1, 0, 1])
        tokens = tokens[:, :1]
    routed = transformer.routes_tokens(
        cfg, params["layers"], kw.get("pack") or tokens.size)
    assert routed == (program != "decode")
    got, new = _paged_step(mod, cfg, params, cache, tokens, q_len, first, **kw)
    with monkeypatch.context() as m:
        m.setattr(transformer, "routes_tokens", lambda *a: False)
        want, old = _paged_step(mod, cfg, params, cache, tokens, q_len, first,
                                **kw)
    rows = q_len > 0
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows],
                               rtol=1e-5, atol=1e-5)
    for name in ("k", "v"):   # the lines of the tokens that exist
        lines = [np.asarray(c[name])[:, :SLOTS * PAGES].reshape(
            cfg.num_hidden_layers, SLOTS, PAGES * PAGE, -1) for c in (new, old)]
        for r in range(SLOTS):
            held = first[r] + q_len[r]
            np.testing.assert_allclose(lines[0][:, r, :held],
                                       lines[1][:, r, :held],
                                       rtol=1e-5, atol=1e-6)
    counts = np.asarray(new["moe_counts"])
    assert counts.shape == (cfg.num_hidden_layers, cfg.num_local_experts)
    assert (counts.sum(axis=1)
            == routed * q_len.sum() * cfg.num_experts_per_tok).all()
    assert not np.asarray(old["moe_counts"]).any()
    assert mod.step_counts(cfg) == {"moe_counts": counts.shape}


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
@pytest.mark.parametrize("family", ["mixtral", "qwen2_moe"])
def test_routed_ffn_chooses_the_einsums_experts(family, kernels):
    """One sparse layer over a flat token axis with padding places: the
    experts the routed layer counted are the ones ``_moe_ffn``'s rule
    chooses for the real tokens (``lax.top_k`` of the float32 router
    logits), and its result on them is the einsum's."""
    mod, cfg, params = _sparse_family(family)
    layer, T = 1, 24
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.normal(size=(1, T, cfg.hidden_size)), jnp.float32)
    real = np.arange(T) % 5 != 3
    p_l = jax.tree.map(lambda a: a[layer], params["layers"])
    want = transformer._moe_ffn(cfg, p_l, h)
    stacked = dict(p_l, **{name: params["layers"][name]
                           for name in transformer.EXPERT_STACKS})
    got, counts = transformer._routed_ffn(cfg, stacked, h, jnp.asarray(real),
                                          layer, kernels)
    np.testing.assert_allclose(np.asarray(got)[0, real],
                               np.asarray(want)[0, real], rtol=1e-5, atol=1e-6)
    logits = np.asarray(h[0], np.float32) @ np.asarray(p_l["w_router"])
    chosen = np.argsort(-logits, axis=-1, kind="stable")[
        :, :cfg.num_experts_per_tok]
    np.testing.assert_array_equal(
        np.asarray(counts),
        np.bincount(chosen[real].reshape(-1), minlength=cfg.num_local_experts))


@pytest.mark.parametrize("norm_topk", [True, False])
def test_route_softmax_topk_is_the_einsums_rule(norm_topk):
    """``route_softmax_topk`` against ``_moe_ffn``'s arithmetic written
    out: top-k of the float32 logits, among equals the lower index
    first (experts 1 and 3 share a router column, so every token ties
    them), then the softmax over the chosen k, or the chosen entries of
    the softmax over all."""
    rng = np.random.default_rng(5)
    T, D, E, K = 32, 16, 6, 2
    w = rng.normal(size=(D, E)).astype(np.float32)
    w[:, 3] = w[:, 1]
    h = rng.normal(size=(T, D)).astype(np.float32)
    experts, gate = transformer.route_softmax_topk(
        jnp.asarray(h), jnp.asarray(w), K, norm_topk=norm_topk)
    logits = np.asarray(jnp.matmul(jnp.asarray(h), jnp.asarray(w),
                                   preferred_element_type=jnp.float32))
    topv, topi = jax.lax.top_k(logits, K)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(topi))
    order = np.argsort(-logits, axis=-1, kind="stable")[:, :K]
    np.testing.assert_array_equal(np.asarray(experts), order)
    tied = (order == 1).any(axis=1) & (order == 3).any(axis=1)
    assert tied.any()
    assert (np.argmax(order[tied] == 1, axis=1)
            < np.argmax(order[tied] == 3, axis=1)).all()
    if norm_topk:
        want = jax.nn.softmax(topv, axis=-1)
    else:
        want = jnp.take_along_axis(jax.nn.softmax(jnp.asarray(logits), axis=-1),
                                   topi, axis=-1)
    np.testing.assert_array_equal(np.asarray(gate), np.asarray(want))
    assert experts.dtype == jnp.int32 and gate.dtype == jnp.float32


@pytest.mark.parametrize("pairs, experts, routed, tile", [
    # LFM2's programs (64 experts, top-4): the C=1 step at 64 rows, the
    # 2048 and 4096 rungs and the padded step: what they had before
    (256, 64, 64, 16), (8192, 64, 64, 128), (16384, 64, 64, 128),
    (32768, 64, 64, 128),
    # and its admission rung (256 places): 16 rows an expert fill one
    # 16-row tile each, so the middle case starts above them
    (1024, 64, 64, 16),
    # Mixtral's (8 experts, top-2): the C=1 step at 16 rows, the 512 rung
    # (128 rows an expert: a whole tile), the 1024 rung, the padded step
    # (256 and 512 rows an expert: a 256-row tile read no faster on the
    # kernel alone, PERF.md section 6, PR 51)
    (32, 8, 8, 16), (1024, 8, 8, 128), (2048, 8, 8, 128), (4096, 8, 8, 128),
    # Mixtral's admission rung (256 places): 64 rows an expert were four
    # 16-row tiles that computed for longer than their weights took to
    # arrive; the middle tile (ISSUE 51)
    (512, 8, 8, 32),
    # SmallThinker's (64 experts, top-6): the padded step (96 rows an
    # expert: seven 16-row tiles before), its 512 and 256 rungs (48, 24)
    # and the C=1 step at 8 rows
    (6144, 64, 64, 32), (3072, 64, 64, 32), (1536, 64, 64, 32),
    (48, 64, 64, 16),
    # DeepSeek-V3's (16 experts held of 256, top-8; 4 slots): its programs
    # come out as they were. 128 pairs to an expert HELD keep the 128-row
    # tile though 8 and 16 arrive on average (a skewed expert then walks
    # few tiles, and any tile hides under 59 MB of weights); under that
    # the rows that ARRIVE decide, 4 an expert at the 128 rung, and not
    # the 64 a held expert has room for; the C=1 step
    (4096, 16, 256, 128), (2048, 16, 256, 128), (1024, 16, 256, 16),
    (32, 16, 256, 16),
    # the same held share of a router under load: 32 rows arrive
    (8192, 16, 256, 128), (8192, 128, 256, 32),
])
def test_grouped_tile_follows_the_rows_an_expert_gets(pairs, experts, routed,
                                                      tile):
    assert serve_kernels.grouped_tile(pairs, experts, routed) == tile
    if routed == experts:   # the router's width is the experts held unless told
        assert serve_kernels.grouped_tile(pairs, experts) == tile


@pytest.mark.parametrize("width, depth, weights, block", [
    # pinned since PR 36 (tests/test_chip_compile.py compiles them):
    # Mixtral's up-projections and down-projection (235 MB of gate and
    # up an expert, 117 MB of down)
    (14336, 4096, 2, 1024), (4096, 14336, 1, 512),
    # LFM2's (gate and up 25.2 MB double-buffered, down 12.6): whole,
    # where 512 gave three and four column blocks (on the kernel alone
    # the whole matrices read no slower, PERF.md section 6, PR 51)
    (1536, 2048, 2, 1536), (2048, 1536, 1, 2048),
    # SmallThinker's (15.7 and 7.9 MB): one column block each, where 512
    # gave 768 two blocks of 384 and 2560 five of 512
    (768, 2560, 2, 768), (2560, 768, 1, 2560),
    # DeepSeek-V3's (117 and 59 MB)
    (2048, 7168, 2, 512), (7168, 2048, 1, 512),
    # a width 512 does not divide, over the 32 MB: the widest multiple
    # of a lane tile under 512 that divides it
    (1920, 4096, 2, 384),
    # over the 32 MB (48) and at them exactly
    (1536, 4096, 2, 512), (1024, 4096, 2, 1024),
])
def test_grouped_block_follows_the_widths(width, depth, weights, block):
    assert serve_kernels.grouped_block(width, depth, weights, 2) == block
    assert width % block == 0
    # what the calls hold of their 48 MB for weights, double-buffered
    assert 2 * weights * depth * block * 2 <= 32 << 20


def _pairs_by_count(tm, T, held, unheld):
    """(experts (T, 2), real (T,)) whose real tokens give the four
    experts ``held`` no row, one row, exactly a tile and a tile and a
    row: column 0 holds the expert with one row, column 1 the two
    with a tile, every other place an expert of ``unheld`` (not held
    here), and the last three tokens are padding."""
    empty, one, whole, over = held
    experts = np.empty((T, 2), np.int32)
    experts[:, 0], experts[:, 1] = unheld
    experts[3, 0] = one
    experts[:tm, 1] = whole
    experts[tm:2 * tm + 1, 1] = over
    real = np.arange(T) < T - 3
    experts[~real] = (one, whole)      # padding routes nowhere
    return experts, real


@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("tm, T", [(16, 64), (32, 160), (128, 288)])
def test_grouped_kernels_match_ragged_dot_at_every_tile(tm, T, activation):
    """``routed_experts_ffn(kernels="pallas")`` (interpret mode) against
    ``kernels="xla"`` at each tile ``grouped_tile`` returns and under
    either gate, the experts
    held (2 to 5) a part of the router's 8 outputs, the weights every
    layer's and addressed by ``layer``, on counts that hold an empty
    expert, an expert with one row, one with exactly a tile and one with
    a tile and a row."""
    k, routed, (lo, hi), L, D, F = 2, 8, (2, 6), 2, 32, 48
    assert serve_kernels.grouped_tile(T * k, hi - lo, routed) == tm
    experts, real = _pairs_by_count(tm, T, range(lo, hi), (0, 7))
    rng = np.random.default_rng(tm)
    h = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(L, hi - lo, D, F)) * 0.2,
                                jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(L, hi - lo, F, D)) * 0.2, jnp.float32)
    out = {}
    for kernels in ("xla", "pallas"):
        out[kernels], counts = transformer.routed_experts_ffn(
            h, jnp.asarray(real), jnp.asarray(experts), weights, w_gate, w_up,
            w_down, experts_held=(lo, hi), routed=routed, layer=jnp.int32(1),
            kernels=kernels, activation=activation)
        np.testing.assert_array_equal(np.asarray(counts), [0, 1, tm, tm + 1])
    np.testing.assert_allclose(out["pallas"], out["xla"], rtol=1e-5, atol=1e-5)
    # the layer addressed, not its neighbour: the same call on layer 1 alone
    alone, _ = transformer.routed_experts_ffn(
        h, jnp.asarray(real), jnp.asarray(experts), weights, w_gate[1],
        w_up[1], w_down[1], experts_held=(lo, hi), routed=routed,
        kernels="pallas", activation=activation)
    np.testing.assert_allclose(out["pallas"], alone, rtol=1e-6, atol=1e-6)
    assert np.abs(np.asarray(alone)[real]).max() > 0
    assert not np.asarray(alone)[~real].any()


def _sorted_tables(group, n, tm, k, T):
    """The layout's tables as the SORT built them (``routed_experts_ffn``
    up to PR 56, kept here as the reference the counting is held to):
    a stable argsort of the pairs by expert, ``searchsorted`` over the
    aligned ends, a scatter of the sorted pairs' tokens and a second
    scatter of their places back to pair order. -> (counts, place,
    source (``T``: a row no pair has), tile_group, n_active)."""
    P = group.shape[0]
    order = jnp.argsort(group, stable=True)
    counts = jnp.sum(jax.nn.one_hot(group, n, dtype=jnp.int32), axis=0)
    tiles = -(-(P + n * (tm - 1)) // tm)
    aligned = -(-counts // tm) * tm
    ends = jnp.cumsum(aligned)
    by_group = group[order]                               # sorted; n at the tail
    g = jnp.minimum(by_group, n - 1)
    rank = jnp.arange(P, dtype=jnp.int32) - (jnp.cumsum(counts) - counts)[g]
    at = jnp.where(by_group < n, (ends - aligned)[g] + rank, tiles * tm)
    source = jnp.full((tiles * tm,), T, jnp.int32).at[at].set(
        (order // k).astype(jnp.int32), mode="drop")
    n_active = ends[-1] // tm
    tile = jnp.arange(tiles, dtype=jnp.int32)
    tile_group = jnp.searchsorted(ends, tile * tm, side="right")
    last = tile_group[jnp.maximum(n_active - 1, 0)]
    tile_group = jnp.minimum(
        jnp.where(tile < n_active, tile_group, last), n - 1)
    place = jnp.zeros((P,), jnp.int32).at[order].set(at.astype(jnp.int32))
    return counts, place, source, tile_group, n_active


@jax.jit
def _token_major_sum(rows, place, held, weights):
    """The weighted sum over the gather ``routed_experts_ffn`` made up
    to PR 58: all the pairs' rows, seen (T, k, D). Its terms are added
    in ``pairs_to_tokens``' order (choice 0, 1, ..., k - 1, each
    product and each addition float32) since PR 59. Up to then it was
    one einsum over k, whose order nobody chose, and k = 2 moved with
    it on the CPU: there the compiler contracts a product and the
    addition after it into one rounding (a fused multiply-add), the
    einsum's dot and the written chain not alike. Jitted, as the
    layer's own is, so that the two are contracted alike."""
    T, k = place.shape
    got = jnp.take(rows, place.reshape(-1), axis=0, mode="clip").reshape(T, k, -1)
    terms = (jnp.where(held[..., None], got, 0.0)
             * jnp.where(held, weights, 0.0)[..., None])
    return functools.reduce(jnp.add, (terms[:, j] for j in range(k)))


def _sorted_routed_ffn(h, real, experts, weights, w_gate, w_up, w_down, *,
                       experts_held, routed, layer, activation):
    """``routed_experts_ffn(kernels="pallas")`` as it stood up to PR 56:
    the sort-built tables, the aligned rows gathered with the rows no
    pair has ZEROED, the same two kernels."""
    T, k = experts.shape
    lo, hi = experts_held
    n = hi - lo
    held = real[:, None] & (experts >= lo) & (experts < hi)
    group = jnp.where(held, experts - lo, n).reshape(-1)
    tm = transformer.routed_tile(T, k, experts_held, routed)
    counts, place, source, tile_group, n_active = _sorted_tables(
        group, n, tm, k, T)
    w_gate, w_up, w_down = (
        w.reshape((-1,) + w.shape[2:]) for w in (w_gate, w_up, w_down))
    rows = jnp.take(h, source, axis=0, mode="fill", fill_value=0)
    tile_group = layer * n + tile_group
    act = serve_kernels.grouped_glu(rows, w_gate, w_up, tile_group, n_active,
                                    tm=tm, activation=activation)
    out = serve_kernels.grouped_down(act, w_down, tile_group, n_active, tm=tm)
    out = _token_major_sum(out, place.reshape(T, k), held, weights)
    return out.astype(h.dtype), counts


@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("tm, T", [(16, 64), (32, 160), (128, 288)])
def test_routed_ffn_is_the_sorted_layouts_bit_for_bit(tm, T, activation):
    """The layer over the layout built by counting, with the rows no
    pair has left as they are gathered, against the layer over the
    sort-built layout with those rows zeroed (PR 56's), on
    ``test_grouped_kernels_match_ragged_dot_at_every_tile``'s cases:
    the kernels are given the same real rows in the same order, no
    other row's result is read, and the weighted sum is the same one,
    so the results are EQUAL, bit for bit."""
    k, routed, (lo, hi), L, D, F = 2, 8, (2, 6), 2, 32, 48
    experts, real = _pairs_by_count(tm, T, range(lo, hi), (0, 7))
    rng = np.random.default_rng(tm)
    h = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(L, hi - lo, D, F)) * 0.2,
                                jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(L, hi - lo, F, D)) * 0.2, jnp.float32)
    args = (h, jnp.asarray(real), jnp.asarray(experts), weights, w_gate, w_up,
            w_down)
    kw = dict(experts_held=(lo, hi), routed=routed, layer=jnp.int32(1),
              activation=activation)
    want, want_counts = _sorted_routed_ffn(*args, **kw)
    got, counts = transformer.routed_experts_ffn(*args, kernels="pallas", **kw)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(got)[real]).max() > 0


@pytest.mark.parametrize("k", [2, 4, 6, 8, 10])
def test_pairs_to_tokens_sums_the_held_pairs(k):
    """``pairs_to_tokens`` against the sum written out in float64: each
    token's held pairs' rows by their weights; a pair that is not held
    adds nothing whatever its place names (a row a grouped matmul never
    wrote: NaN here), nor does a token with no held pair. And its BITS
    (PR 59): each product and each addition float32, the terms added in
    choice order j = 0, 1, ..., k - 1, left to right, against the same
    terms added one by one in NumPy."""
    T, R, D = 24, 40, 16
    rng = np.random.default_rng(k)
    rows = rng.normal(size=(R, D)).astype(np.float32)
    held = rng.random((T, k)) < 0.6
    held[0] = False
    place = rng.integers(0, R - 4, size=(T, k)).astype(np.int32)
    place[~held] = rng.integers(R - 4, R, size=int((~held).sum()))
    rows[R - 4:] = np.nan                              # rows nobody wrote
    weights = rng.uniform(0.1, 1.0, size=(T, k)).astype(np.float32)
    args = (jnp.asarray(rows), jnp.asarray(place), jnp.asarray(held),
            jnp.asarray(weights))
    got = np.asarray(transformer.pairs_to_tokens(*args))
    want = np.zeros((T, D))
    in_order = np.zeros((T, D), np.float32)
    for t in range(T):
        for j in range(k):
            if held[t, j]:
                want[t] += np.float64(weights[t, j]) * rows[place[t, j]]
            term = (rows[place[t, j]] * weights[t, j] if held[t, j]
                    else np.zeros(D, np.float32))
            assert term.dtype == np.float32
            in_order[t] = term if j == 0 else in_order[t] + term
    assert got.dtype == np.float32 and not got[0].any()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # The CPU's compiler contracts a product and the addition after it
    # into one rounding (a fused multiply-add: under
    # XLA_FLAGS=--xla_cpu_max_isa=AVX the jitted call gives the loop's
    # bits here too, and on a v5e it gives them as it is, PERF.md
    # section 6, PR 59), so the bits are read with each jnp operation
    # dispatched on its own, and the jitted program is held to the same
    # chain: k - 1 additions, no reduction and no dot whose order a
    # compiler chooses.
    with jax.disable_jit():
        one_by_one = np.asarray(transformer.pairs_to_tokens(*args))
    np.testing.assert_array_equal(one_by_one, in_order)
    primitives = [e.primitive.name for e in jax.make_jaxpr(
        transformer.pairs_to_tokens.__wrapped__)(*args).jaxpr.eqns]
    assert primitives.count("add") == k - 1, primitives
    assert not {"reduce_sum", "dot_general"} & set(primitives), primitives


@pytest.mark.parametrize("case, T, k, held, routed, tm, real, on", [
    # the five routed cells' steps: (places, experts a token, experts
    # held as a range of the router's outputs, the router's outputs,
    # the tile ``routed_tile`` gives them), three places in four real
    ("mixtral", 1024, 2, (0, 8), 8, 128, 0.75, None),
    ("deepseek", 512, 8, (0, 16), 256, 128, 0.75, None),
    ("smallthinker", 1024, 6, (0, 64), 64, 32, 0.75, None),
    ("qwen3_next", 64, 10, (0, 128), 512, 16, 0.75, None),
    ("lfm2", 64, 4, (0, 64), 64, 16, 0.75, None),
    # the edges: P = 111 is no multiple of the counting block, and the
    # held range a strict inner part of the router's outputs
    ("ragged", 37, 3, (2, 7), 9, 16, 0.75, None),
    ("no_real_token", 37, 3, (2, 7), 9, 16, 0.0, None),
    ("every_place_real", 160, 2, (0, 4), 4, 32, 1.0, None),
    ("every_pair_on_one_expert", 160, 2, (0, 4), 4, 32, 0.75, (1, 1)),
    ("no_pair_held", 160, 2, (4, 8), 8, 16, 0.75, (0, 3)),
    # an XLA-path layout: a tile of one row, the rows end to end
    ("rows_end_to_end", 37, 3, (2, 7), 9, 1, 0.75, None),
])
def test_pair_layout_counts_what_the_sort_sorted(case, T, k, held, routed, tm,
                                                 real, on):
    """``pair_layout`` and ``tile_experts`` (a one-hot's running count,
    one scatter, a compare and a sum) against the tables the stable
    sort, ``searchsorted`` and the two scatters built: the tokens per
    expert, the tiles' experts and the active tiles EQUAL; every row a
    pair has names the same token, and a row no pair has names SOME
    token (it is gathered, computed where its tile is active, and never
    read); every held pair's place equal."""
    lo, hi = held
    n = hi - lo
    if case in ("mixtral", "deepseek", "smallthinker", "qwen3_next", "lfm2"):
        assert transformer.routed_tile(T, k, held, routed) == tm
    rng = np.random.default_rng(len(case))
    if on is None:   # k distinct outputs of the router a token
        experts = np.argsort(rng.random((T, routed)), axis=1)[:, :k]
    else:
        experts = np.broadcast_to(np.asarray(on), (T, k))
    is_real = rng.random(T) < real
    is_held = is_real[:, None] & (experts >= lo) & (experts < hi)
    group = jnp.asarray(np.where(is_held, experts - lo, n).reshape(-1),
                        jnp.int32)
    want_counts, want_place, want_source, want_tiles, want_active = (
        np.asarray(a) for a in _sorted_tables(group, n, tm, k, T))
    counts, place, source, ends = transformer.pair_layout(group, n, tm, k)
    tile_group, n_active = transformer.tile_experts(
        ends, source.shape[0] // tm, tm)
    assert all(a.dtype == jnp.int32 for a in (counts, place, source,
                                              tile_group, n_active))
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    assert int(n_active) == int(want_active)
    np.testing.assert_array_equal(np.asarray(tile_group), want_tiles)
    source, place = np.asarray(source), np.asarray(place)
    assert source.shape == want_source.shape
    has_pair = want_source < T
    np.testing.assert_array_equal(source[has_pair], want_source[has_pair])
    assert ((0 <= source) & (source < T)).all()
    flat = is_held.reshape(-1)
    assert int(flat.sum()) == int(has_pair.sum()) == int(want_counts.sum())
    np.testing.assert_array_equal(place[flat], want_place[flat])
    assert ((0 <= place) & (place < len(source))).all()


#: tokens per expert by the row tile, and the tiles laid out past the
#: last that holds a row: what the grouped matmuls' weight fetches
#: (serve/kernels ``grouped_fetches``) are walked over
_RUNS = {
    "empty_experts": (lambda tm: [0, 3, 0, tm + 1, 0], 2),
    "one_tile_beside_eleven": (lambda tm: [1, 11 * tm], 1),
    "no_row": (lambda tm: [0, 0, 0], 3),
    "one_tile": (lambda tm: [0, tm, 0], 2),
    "every_tile": (lambda tm: [2 * tm, 1, tm + 1], 0),
}


def _tiles_of(counts, tm, spare, first=0):
    """(``tile_group``, ``n_active``, each real row's place) as
    ``routed_experts_ffn`` lays ``counts`` tokens per expert out in
    ``tm``-row tiles, ``spare`` tiles past the last that holds a row
    (they repeat its expert), the experts' indices from ``first``."""
    counts = np.asarray(counts)
    aligned = -(-counts // tm) * tm
    ends = np.cumsum(aligned)
    n_active = int(ends[-1]) // tm
    tile = np.arange(n_active + spare)
    group = np.searchsorted(ends, tile * tm, side="right")
    group = np.where(tile < n_active, group, group[max(n_active - 1, 0)])
    at = np.concatenate([s + np.arange(c) for s, c in
                         zip(ends - aligned, counts)]).astype(np.int64)
    return (first + np.minimum(group, len(counts) - 1)).astype(np.int32), \
        n_active, at


@pytest.mark.parametrize("first", [0, 7])
@pytest.mark.parametrize("blocks", [1, 3])
@pytest.mark.parametrize("case", list(_RUNS))
def test_grouped_fetches_take_every_block_once_a_run_ahead(case, blocks, first):
    """The weight fetches of a grouped call as a function of
    ``tile_group`` and ``n_active`` alone, walked as the kernel walks
    its grid (column blocks outermost, ``fetch_of_step`` at a run's
    first tile): every run's block of every column block is fetched
    once, in the grid's order, into alternating slots; a copy is
    started into a slot only when none is in flight there and is waited
    for exactly once; every tile that holds a row reads its own
    expert's block of its own column block; a tile past ``n_active``
    does nothing, nor does a call no token chose."""
    counts, spare = _RUNS[case]
    tile_group, n_active, _ = _tiles_of(counts(16), 16, spare, first)
    sched = serve_kernels.grouped_fetches(jnp.asarray(tile_group),
                                          jnp.int32(n_active))
    is_first, run, following, runs = (np.asarray(a) for a in sched)
    assert all(a.dtype == np.int32 for a in (is_first, run, following, runs))
    assert not is_first[n_active:].any()
    flying, held, started = {}, {}, []
    for j in range(blocks):
        for t in range(len(tile_group)):
            slot, opens, followed, column = (
                int(x) for x in serve_kernels.fetch_of_step(
                    j, int(run[t]), int(runs[0]), blocks))
            if is_first[t]:
                own = (int(tile_group[t]), j)
                fetches = [(slot, own)] * opens + [
                    (1 - slot, (int(following[t]), column))] * followed
                for into, block in fetches:
                    assert into not in flying, (j, t, into)
                    flying[into] = block
                    started.append((into, block))
                assert flying[slot] == own, (j, t)
                held[slot] = flying.pop(slot)
            if t < n_active:
                assert held[slot] == (int(tile_group[t]), j), (j, t)
    assert not flying                                  # starts equal waits
    hit = [first + e for e, c in enumerate(counts(16)) if c]
    assert [block for _, block in started] == [
        (e, j) for j in range(blocks) for e in hit]
    assert [slot for slot, _ in started] == [
        i % 2 for i in range(len(started))]
    assert int(runs[0]) == len(hit)


@pytest.mark.parametrize("tm, activation, D, F", [
    # the up-projections in two column blocks and the down-projection
    # in one; then one and three
    (16, "silu", 128, 256), (32, "relu", 384, 128),
    (128, "silu", 128, 256), (128, "relu", 384, 128)])
@pytest.mark.parametrize("case", list(_RUNS))
def test_grouped_kernels_read_the_blocks_they_fetched(case, tm, activation,
                                                      D, F, monkeypatch):
    """``grouped_glu`` / ``grouped_down`` (interpret mode: the copies,
    the slots and the semaphores run as written) against
    ``lax.ragged_dot`` on the counts the fetches are walked over, at
    every tile ``grouped_tile`` returns, with weight blocks of 128
    columns so that a call walks its runs once a column block, the
    experts addressed from an offset into a longer stack."""
    monkeypatch.setattr(serve_kernels, "grouped_block", lambda *a: 128)
    counts, spare = _RUNS[case]
    counts, first = np.asarray(counts(tm)), 2
    tile_group, n_active, at = _tiles_of(counts, tm, spare, first)
    G = first + len(counts) + 1
    rng = np.random.default_rng(tm)
    x = jnp.asarray(rng.normal(size=(int(counts.sum()), D)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(G, D, F)) * 0.2, jnp.float32)
                    for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(G, F, D)) * 0.2, jnp.float32)
    rows = jnp.zeros((len(tile_group) * tm, D), jnp.float32).at[at].set(x)
    act = serve_kernels.grouped_glu(
        rows, w_gate, w_up, jnp.asarray(tile_group), jnp.int32(n_active),
        tm=tm, activation=activation)
    out = serve_kernels.grouped_down(
        act, w_down, jnp.asarray(tile_group), jnp.int32(n_active), tm=tm)
    assert act.shape == (len(rows), F) and out.shape == (len(rows), D)
    sizes = np.zeros(G, np.int32)
    sizes[first:first + len(counts)] = counts
    dot = lambda a, w: jax.lax.ragged_dot(
        a, w, group_sizes=jnp.asarray(sizes),
        preferred_element_type=jnp.float32)
    want = getattr(jax.nn, activation)(dot(x, w_gate)) * dot(x, w_up)
    np.testing.assert_allclose(np.asarray(act)[at], want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out)[at], dot(want, w_down),
                               rtol=1e-4, atol=1e-3)


def test_grouped_calls_are_traced_once_a_shape():
    """A step program calls the grouped matmuls once a sparse layer:
    every call site of one shape shares ONE traced function (the
    kernel's body is traced and lowered once a program, not once a
    layer), and another tile or gate is another function."""
    tile_group, n_active, _ = _tiles_of([3, 20], 16, 1)
    rows = jnp.ones((len(tile_group) * 16, 32), jnp.float32)
    w = jnp.ones((2, 32, 48), jnp.float32)

    def layers(rows, w, activation="silu"):
        acts = [serve_kernels.grouped_glu(
            rows + l, w, w, jnp.asarray(tile_group), jnp.int32(n_active),
            tm=16, activation=activation) for l in range(3)]
        return sum(acts)

    calls = [e for e in jax.make_jaxpr(layers)(rows, w).eqns
             if e.primitive.name in ("pjit", "jit")
             and "pallas_call" in str(e.params["jaxpr"])]
    assert len(calls) == 3
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1
    relu = [e for e in jax.make_jaxpr(
        functools.partial(layers, activation="relu"))(rows, w).eqns
        if e.primitive.name in ("pjit", "jit")
        and "pallas_call" in str(e.params["jaxpr"])]
    assert id(relu[0].params["jaxpr"]) != id(calls[0].params["jaxpr"])


@pytest.mark.parametrize("tokens, routed", [
    (16, False),     # the C=1 step at 16 slots: 4 pairs an expert, the einsum
    (256, True),     # the admission rung: 64 an expert, whatever its tile
    (512, True), (1024, True), (2048, True),
])
def test_routes_tokens_does_not_move_with_the_tile(tokens, routed):
    """Mixtral's programs at published widths take the grouped form
    from 16 pairs an expert on, as they did when the tile was 16 there
    (the condition is stated apart from ``grouped_tile``, ISSUE 51)."""
    cfg = mixtral.mixtral_8x7b(num_hidden_layers=1)
    layers = dict.fromkeys(transformer.EXPERT_STACKS)
    assert transformer.routes_tokens(cfg, layers, tokens) == routed
    assert transformer.routed_tile(
        tokens, *transformer.expert_routing(cfg)
    ) == serve_kernels.grouped_tile(2 * tokens, 8)


@pytest.mark.parametrize("family, kw, tokens, tile", [
    # each cell's programs at published widths: the C=1 step, the
    # narrowest rung, the padded step
    ("lfm2_moe", {}, 64, 16), ("lfm2_moe", {}, 256, 16),
    ("lfm2_moe", {}, 2048, 128), ("lfm2_moe", {}, 8192, 128),
    ("deepseek_v3", {"experts_held": (0, 16)}, 4, 16),
    ("deepseek_v3", {"experts_held": (0, 16)}, 128, 16),
    ("deepseek_v3", {"experts_held": (0, 16)}, 256, 128),
    ("deepseek_v3", {"experts_held": (0, 16)}, 512, 128),
    ("smallthinker", {}, 8, 16), ("smallthinker", {}, 256, 32),
    ("smallthinker", {}, 1024, 32),
    ("mixtral", {}, 256, 32), ("mixtral", {}, 2048, 128),
])
def test_a_familys_expert_routing_gives_its_steps_tile(family, kw, tokens, tile):
    """``expert_routing(cfg)``, which the family's step hands
    ``routed_experts_ffn`` and the engine reads for the host's count of
    tiles (``InferenceEngine.step_tile``), gives ``routed_tile`` the
    pairs, the experts held and the router's outputs of the cell's
    programs."""
    import importlib

    fam = importlib.import_module(f"flexflow_tpu.models.{family}")
    cfg = (fam.mixtral_8x7b if family == "mixtral" else fam.config)(**kw)
    assert transformer.routed_tile(tokens, *fam.expert_routing(cfg)) == tile


@pytest.mark.parametrize("tile, tiles", [(16, 10), (32, 6), (64, 5), (128, 4)])
def test_scheduler_counts_the_tiles_the_tokens_fill(tile, tiles):
    """``SchedulerStats.note_expert_counts`` under a step's tile: an
    expert with no token fills none, the others their tokens rounded up
    to tiles; a layer that did not route is not counted."""
    from flexflow_tpu.metrics import SchedulerStats

    stats = SchedulerStats()
    stats.note_expert_counts(
        np.array([[0, 1, 16, 17, 96], [0, 0, 0, 0, 0]]), tile)
    assert (stats.moe_pairs, stats.moe_experts_hit, stats.moe_experts_held,
            stats.moe_tiles) == (130, 4, 5, tiles)

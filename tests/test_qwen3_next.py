"""Qwen3-Next on the paged serving path (models/qwen3_next.py) against
its plain reference (benchmarks/references/qwen3_next.py, the one copy;
imported by path), at a tiny size on the CPU with the family's own
seeded weights (decays from A in (0, 16) and dt in (0.001, 0.1), taps
of order 1/sqrt(4)) and the zero-centred norms' weights moved off zero
(0.1 normal: ``1 + w`` and ``w`` are then told apart): one period
[L, L, L, F], 2 key heads for 4 value
heads of 16, 4 / 2 softmax heads of 32 with 8 channels rotated, 16
experts of width 32 chosen 3 a token beside a gated shared expert.

Tolerances, each with its reason.

DELTA RULE: the grouped rule (2 value heads a key head) against the
recurrence token by token in float64 on q and k repeated a value head:
max|d| / max|want| under 1e-5, the limit ``tests/test_olmo_hybrid.py``
holds the chunk form to (sound float32 reads 2e-6; a bfloat16 state
2e-3). The grouped call against the same functions on repeated q and k
(equal head counts: Olmo's path, whose jaxprs are the parent's) and
the Pallas kernel against the XLA form: BITWISE, they compute the same
sums in the same order.

LOGITS: rms(served - reference) / rms(reference) a judged row, float32
model, pool and state: under 2e-5, the limit Olmo's tests hold; sound
reads 4e-7 (my CPU run, PR 53). A routing flip, a missing gate, a head
rotated whole or a norm scaled by ``w`` reads 1e-2 and more.

SHARES: four quarter ranges of the experts, the shared expert counted
once, against the reference's uncut block: 2e-6 of the block's own
size (sums in another order).
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import olmo_hybrid
from flexflow_tpu.models import qwen3_next as fam
from flexflow_tpu.models import transformer
from flexflow_tpu.serve.engine import InferenceEngine

from family_cases import *  # noqa: F401,F403 (the cases every family answers)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DELTA_LIMIT = 1e-5
LOGITS_LIMIT = 2e-5
PAGE, CHUNK, SLOTS = 16, 16, 4   # the tiny serving configuration's (conftest.py)


def _reference():
    path = os.path.join(ROOT, "benchmarks", "references", "qwen3_next.py")
    spec = importlib.util.spec_from_file_location("reference_qwen3_next", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _reference()


def _file_config(cfg, held=None):
    """The tiny preset as a configuration FILE's keys: what the
    reference reads."""
    lo, hi = held or cfg.held
    return dict(
        num_hidden_layers=cfg.num_hidden_layers,
        full_attention_interval=cfg.full_attention_interval,
        rms_norm_eps=cfg.norm_eps, hidden_size=cfg.hidden_size,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        partial_rotary_factor=cfg.rotary_pct, rope_theta=cfg.rope_theta,
        linear_num_key_heads=cfg.linear_num_key_heads,
        linear_num_value_heads=cfg.linear_num_value_heads,
        linear_key_head_dim=cfg.linear_key_head_dim,
        linear_value_head_dim=cfg.linear_value_head_dim,
        num_experts=hi - lo, router_outputs=cfg.num_experts,
        experts_held=[lo, hi], num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.moe_norm_topk)


def _off_zero(tree, key):
    """The zero-centred norms' weights at 0.1 normal."""
    out = {}
    for i, (name, leaf) in enumerate(tree.items()):
        k = jax.random.fold_in(key, i)
        if isinstance(leaf, dict):
            out[name] = _off_zero(leaf, k)
        elif name.endswith("norm_w"):
            out[name] = 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
        else:
            out[name] = leaf
    return out


def _draw(key, cfg):
    return jax.jit(lambda key: _off_zero(fam.init_params(key, cfg),
                                         jax.random.fold_in(key, 5)))(key)


# routed experts behind a recurrent mixer: both beside attention
FAMILIES = {"qwen3_next": Family(
    fam, ALWAYS | {"ff.mixer", "ff.moe.route"}, draw=_draw)}


@pytest.fixture(scope="module")
def tiny(tiny_servers):
    return tiny_servers.params(fam, draw=_draw)


def _feed(eng, rows, chunk):
    """One ``run_mixed`` step: ``rows`` maps slot -> (tokens, first
    position). Returns the logits (slots, vocab) at each row's last
    token."""
    R = eng.num_slots
    toks = np.zeros((R, chunk), np.int32)
    pos = np.full((R, chunk), eng.scratch_pos, np.int32)
    idx = np.zeros((R,), np.int32)
    for r, (t, lo) in rows.items():
        toks[r, :len(t)] = t
        pos[r, :len(t)] = np.arange(lo, lo + len(t))
        idx[r] = len(t) - 1
        assert eng.pager.ensure(r, lo + len(t))
    ones = np.ones(R, np.float32)
    _, logits = eng.run_mixed(
        np.zeros(R, np.int32), toks, np.zeros(R, bool), pos, idx,
        jax.random.PRNGKey(0), np.ones(R, bool), ones, ones,
        np.zeros(R, np.int32), with_logits=True)
    return np.asarray(logits, np.float32)


def _rms_share(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


# --- (1) the served path against the reference ------------------------------


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_served_logits_match_the_reference(tiny, kernels, tiny_servers):
    """Chunked prefill of one row (a ragged last chunk), mixed steps in
    which it decodes while another prefills (the recurrence for the row
    of one token, the chunk form for the other), then pure decode steps
    (under ``pallas`` the state kernel at 2 value heads a key head,
    interpreted): every row the server would sample from, against the
    reference's full forward pass, on the packed rungs of the ladder
    (the file's kept servers: the Pallas one is the cross-family
    cases')."""
    cfg, params = tiny
    eng = tiny_servers(fam, draw=_draw, kernels=kernels).engine
    assert eng.pack_ladder(CHUNK) == (16, 32)
    assert eng.cache["state"].shape == (3, SLOTS, 4, 16, 16)
    assert eng.cache["state"].dtype == jnp.float32
    assert eng.cache["conv"].shape == (3, 3, SLOTS, 2 * 2 * 16 + 4 * 16)
    assert eng.cache["k"].shape[0] == 1 and eng.cache["k"].shape[-1] == 2 * 32
    rng = np.random.default_rng(1)
    seqs = {r: rng.integers(0, cfg.vocab_size, 60).tolist() for r in (0, 2)}
    judged, done = {}, {0: 0, 2: 0}

    def step(chunk, feed):
        rows = {r: (seqs[r][done[r]:done[r] + n], done[r]) for r, n in feed.items()}
        logits = _feed(eng, rows, chunk)
        for r, n in feed.items():
            done[r] += n
            judged[(r, done[r] - 1)] = logits[r]

    while done[0] < 39:                        # row 0 prefills alone: 16, 16, 7
        step(CHUNK, {0: min(CHUNK, 39 - done[0])})
    while done[2] < 45:                        # row 0 decodes, row 2 prefills
        step(CHUNK, {0: 1, 2: min(CHUNK, 45 - done[2])})
    for _ in range(4):                         # both decode
        step(1, {0: 1, 2: 1})
    want = reference.forward(
        params, _file_config(cfg), np.asarray([seqs[0], seqs[2]]))
    for r in range(eng.num_slots):
        eng.pager.release(r)
    worst = max(_rms_share(got, want[r // 2, t]) for (r, t), got in judged.items())
    assert len(judged) == 3 + 2 * 3 + 2 * 4 and worst < LOGITS_LIMIT, worst


# --- (2) the delta rule with a group of value heads a key head ---------------


def _delta_inputs(rng, R, T, decay, Hk=2, H=4, dk=16, dv=16):
    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    f32 = np.float32
    q = (unit(rng.standard_normal((R, T, Hk, dk))) * dk ** -0.5).astype(f32)
    k = unit(rng.standard_normal((R, T, Hk, dk))).astype(f32)
    v = rng.standard_normal((R, T, H, dv)).astype(f32)
    g = (np.log(decay) * rng.uniform(0.5, 1.5, (R, T, H))).astype(f32)
    b = rng.uniform(0.0, 1.0, (R, T, H)).astype(f32)
    return q, k, v, g, b


def _token_by_token(q, k, v, g, b, S, n):
    """The reference's token loop over one row's first ``n`` tokens in
    float64, q and k a VALUE head. -> (o (n, H, dv), the state)."""
    S = S.astype(np.float64).copy()
    o = np.zeros((n,) + v.shape[1:])
    for t in range(n):
        S *= np.exp(g[t].astype(np.float64))[:, None, None]
        u = b[t][:, None] * (v[t] - np.einsum("hde,hd->he", S, k[t]))
        S += k[t][:, :, None] * u[:, None, :]
        o[t] = np.einsum("hde,hd->he", S, q[t])
    return o, S


@pytest.mark.parametrize("C, decay", [(128, 0.5), (1, 0.9)],
                         ids=["c128-half", "c1"])
def test_the_grouped_rule_is_the_recurrence_and_olmos_on_repeated_heads(C, decay):
    """The chunk form (two sub-chunks at 128) and the recurrence form
    with 2 value heads a key head: the reference's token loop, and to
    the bit what the same functions give at equal head counts (Olmo's
    path) on q and k repeated a value head. Ragged rows: a full one
    that carries its state, a fresh one, one with no real token (its
    state bitwise unchanged), one of one token."""
    rng = np.random.default_rng(C)
    q, k, v, g, b = _delta_inputs(rng, 4, C, decay)
    state = rng.standard_normal((4, 4, 16, 16)).astype(np.float32)
    count = np.asarray([C, C * 5 // 8 + 1, 0, 1], np.int32)
    fresh = np.asarray([False, True, False, False])
    rest = tuple(map(jnp.asarray, (v, g, b, state, count, fresh)))
    o, s = map(np.asarray, olmo_hybrid.gated_delta(jnp.asarray(q), jnp.asarray(k), *rest))
    qv, kv = (np.repeat(x, 2, axis=2) for x in (q, k))
    o_eq, s_eq = olmo_hybrid.gated_delta(jnp.asarray(qv), jnp.asarray(kv), *rest)
    np.testing.assert_array_equal(o, np.asarray(o_eq))
    np.testing.assert_array_equal(s, np.asarray(s_eq))
    np.testing.assert_array_equal(s[2], state[2])
    for r in (0, 1, 3):
        n = count[r]
        s0 = np.zeros_like(state[r]) if fresh[r] else state[r]
        want_o, want_s = _token_by_token(qv[r], kv[r], v[r], g[r], b[r], s0, n)
        assert np.abs(o[r, :n] - want_o).max() / np.abs(want_o).max() < DELTA_LIMIT
        assert np.abs(s[r] - want_s).max() / np.abs(want_s).max() < DELTA_LIMIT


@pytest.mark.parametrize("heads, dv", [((2, 4), 16), ((2, 4), 64)],
                         ids=["group2", "group2-packed"])
def test_the_state_kernel_is_the_rule_at_one_column(heads, dv):
    """``kernels.gdn_recur_c1`` (interpreted) over a stack of two
    layers against ``gated_delta`` at C = 1: a group of value heads a
    key head, and the same with two heads side by side on the lanes (dv
    64: a pair shares one key head). Equal head counts are Olmo's tests'
    (tests/test_olmo_hybrid.py)."""
    Hk, H = heads
    rng = np.random.default_rng(dv)
    q, k, v, g, b = _delta_inputs(rng, 3, 1, 0.9, Hk=Hk, H=H, dv=dv)
    p = olmo_hybrid.lane_pack(H, dv)
    stack = jnp.asarray(rng.standard_normal((2, 3, H // p, 16, p * dv)), jnp.float32)
    count = jnp.asarray([1, 0, 1], jnp.int32)
    fresh = jnp.asarray([False, False, True])
    token = tuple(jnp.asarray(x[:, 0]) for x in (q, k, v, g, b))
    o, after = olmo_hybrid.recurrence_c1(*token, stack, 1, count, fresh)
    want_o, want_s = olmo_hybrid.gated_delta(
        *map(jnp.asarray, (q, k, v, g, b)), stack[1], count, fresh)
    np.testing.assert_array_equal(np.asarray(after[0]), np.asarray(stack[0]))
    np.testing.assert_array_equal(np.asarray(after[1, 1]), np.asarray(stack[1, 1]))
    np.testing.assert_allclose(np.asarray(after[1]), np.asarray(want_s), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o)[[0, 2]], np.asarray(want_o)[[0, 2], 0],
                               rtol=0, atol=1e-6)


# --- (3) the share ties to the model -----------------------------------------


def test_four_shares_add_up_to_the_uncut_block(tiny):
    """The guide's section 4: four chips each told a quarter of the
    experts route over all 16 and compute their own part; the parts,
    with the shared expert that every chip computes alike counted once,
    are the uncut reference's whole sparse block. (The grouped Pallas
    calls against ``lax.ragged_dot``: tests/test_moe.py and the logits
    test above.)"""
    cfg, params = tiny
    layer = 2
    w = jax.tree.map(lambda a: a[layer], params["sparse"])
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((24, cfg.hidden_size)), jnp.float32)
    h = transformer._norm(cfg, x, w["mlp_norm_w"], None)
    real = jnp.ones((24,), bool)
    total = jnp.zeros_like(x)
    n = cfg.num_experts // 4
    for lo in range(0, cfg.num_experts, n):
        share = dataclasses.replace(cfg, experts_held=(lo, lo + n))
        p = dict(w, **{name: w[name][lo:lo + n] for name in ("w_gate", "w_up", "w_down")})
        out, counts = fam.sparse_ffn(share, p, h, real)
        assert counts.shape == (n,)
        total = total + out - fam.shared_expert(cfg, p, h)
    total = total + fam.shared_expert(cfg, w, h)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.moe(_file_config(cfg), params["sparse"], layer, x)
    want = np.asarray(want)
    assert np.abs(np.asarray(total) - want).max() / np.abs(want).max() < 2e-6
    # and the reference given one share is that share's part
    with jax.default_matmul_precision("highest"):
        part, _ = reference.moe(
            _file_config(cfg, (n, 2 * n)),
            dict(params["sparse"], **{name: params["sparse"][name][:, n:2 * n]
                                      for name in ("w_gate", "w_up", "w_down")}),
            layer, x, shared=False)
    share = dataclasses.replace(cfg, experts_held=(n, 2 * n))
    p = dict(w, **{name: w[name][n:2 * n] for name in ("w_gate", "w_up", "w_down")})
    got = fam.sparse_ffn(share, p, h, real)[0] - fam.shared_expert(cfg, p, h)
    assert np.abs(np.asarray(got) - np.asarray(part)).max() / np.abs(want).max() < 2e-6


# --- (4) the full layer's parts, one by one ----------------------------------


def test_a_heads_query_columns_are_its_query_then_its_gate(tiny):
    cfg, params = tiny
    p = jax.tree.map(lambda a: a[0], params["attn"])
    h = jnp.asarray(np.random.default_rng(0).standard_normal((3, 5, cfg.hidden_size)),
                    jnp.float32)
    q, gate = fam.gated_queries(cfg, p, h)
    H, d = cfg.num_attention_heads, cfg.head_dim
    full = np.asarray(h) @ np.asarray(p["wq"])
    for head in range(H):
        cols = full[..., head * 2 * d:(head + 1) * 2 * d]
        np.testing.assert_allclose(np.asarray(q[..., head, :]), cols[..., :d], atol=1e-5)
        np.testing.assert_allclose(np.asarray(gate[..., head, :]), cols[..., d:], atol=1e-5)


def test_a_quarter_of_a_head_rotates_and_the_rest_passes(tiny):
    """8 of 32 channels here (64 of 256 as published): rotate-half
    pairing INSIDE the rotated channels, at ``rope_theta`` 1e7."""
    cfg, _ = tiny
    assert (cfg.rotary_pct, cfg.rope_theta) == (0.25, 1e7)
    pos = jnp.asarray([[0, 1, 7, 300]])
    cos, sin = transformer.rope_freqs(cfg, pos)
    assert cos.shape == (1, 4, 8)
    x = np.random.default_rng(1).standard_normal((1, 4, 2, 32)).astype(np.float32)
    got = np.asarray(transformer.apply_rope(jnp.asarray(x), cos, sin))
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(got[:, 0], x[:, 0])          # position 0
    inv = 1e7 ** (-np.arange(4) / 4.0)
    ang = np.asarray(pos, np.float64)[..., None] * inv         # (1, 4, 4)
    a, b = x[..., :4], x[..., 4:8]
    c, s = np.cos(ang)[:, :, None], np.sin(ang)[:, :, None]
    want = np.concatenate([a * c - b * s, b * c + a * s], -1)
    np.testing.assert_allclose(got[..., :8], want, atol=1e-5)


def test_the_head_norms_scale_by_one_plus_w(tiny):
    """A head's own 32 values, float32, ``1 + w``: w = 0 is the plain
    RMSNorm, and the recurrent layer's output norm alone scales by w
    (held by the logits test: its scale is drawn one, the others' w
    0.1 normal)."""
    cfg, params = tiny
    assert cfg.norm_plus_one and cfg.norm_eps == 1e-6
    w = params["attn"]["q_norm_w"][0]
    x = np.random.default_rng(2).standard_normal((2, 3, 4, 32)).astype(np.float32)
    plain = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    got = np.asarray(transformer._norm(cfg, jnp.asarray(x), w, None))
    np.testing.assert_allclose(got, plain * (1 + np.asarray(w)), atol=1e-5)
    zero = np.asarray(transformer._norm(cfg, jnp.asarray(x), jnp.zeros_like(w), None))
    np.testing.assert_allclose(zero, plain, atol=1e-5)
    assert np.abs(got - zero).max() > 1e-2


# --- (5) the configuration: from_hf and the refusals -------------------------

# the catalog's row of the published config.json
# (/opt/skills/guides/model-configs/architectures.jsonl), copied
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def test_from_hf_reads_the_published_keys():
    cfg = fam.from_hf(PUBLISHED, dtype=jnp.bfloat16)
    assert cfg.kinds == ((("gdn", "sparse"),) * 3 + (("attn", "sparse"),)) * 12
    assert [cfg.count(g) for g in fam.GROUPS] == [36, 12, 48]
    assert (cfg.gdn_heads, cfg.conv_dim) == ((16, 32, 128, 128), 8192)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (16, 2, 256)
    assert (cfg.rotary_pct, cfg.rope_theta, cfg.norm_eps) == (0.25, 1e7, 1e-6)
    assert cfg.norm_plus_one and not cfg.tie_word_embeddings
    assert fam.expert_routing(cfg) == (10, (0, 512), 512)
    shapes = fam._group_shapes(cfg, "gdn")
    assert shapes["w_qkvz"] == (2048, 12288) and shapes["w_gates"] == (2048, 64)
    assert fam._group_shapes(cfg, "attn")["wq"] == (2048, 16 * 512)
    # 80 B: 48 layers' 512 experts of 3.146 M are 77.3 B of them
    assert abs(fam.num_params(cfg) / 1e9 - 79.67) < 0.05


def test_from_hf_reads_the_benchmark_configuration():
    """The chip's share: 128 experts of the router's 512, a quarter of
    the vocabulary, three periods; every other published key unchanged."""
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs", "qwen3-next-80b-a3b.json")) as f:
        hf = json.load(f)
    cfg = fam.from_hf(hf, dtype=jnp.bfloat16)
    assert set(hf["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key not in hf["reduced"]:
            assert hf[key] == value, key
    assert [cfg.count(g) for g in fam.GROUPS] == [9, 3, 12]
    assert fam.expert_routing(cfg) == (10, (0, 128), 512)
    assert cfg.vocab_size == 37984 == 151936 // 4 and cfg.state_slots == 64
    # 9 x 440.6 M + 3 x 434.1 M + 155.6 M: 5423 M parameters
    assert abs(fam.num_params(cfg) / 1e6 - 5423) < 1
    assert transformer.layer_runs(cfg.kinds) == [
        (("gdn", "sparse"), {"gdn": 3 * i, "sparse": 4 * i}, 3) if kind == "gdn" else
        (("attn", "sparse"), {"attn": i, "sparse": 4 * i + 3}, 1)
        for i in range(3) for kind in ("gdn", "attn")]


@pytest.mark.parametrize("key, value, names", [
    ("mlp_only_layers", [3], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"),
])
def test_from_hf_refuses_what_is_not_built(key, value, names):
    with pytest.raises(NotImplementedError, match=names):
        fam.from_hf(dict(PUBLISHED, **{key: value}))


def test_a_range_that_is_not_the_count_held_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        fam.from_hf(dict(PUBLISHED, num_experts=128, router_outputs=512,
                         experts_held=[0, 64]))
    with pytest.raises(ValueError, match="experts_held"):
        fam.config(experts_held=(500, 600))


@pytest.mark.parametrize("serving, model, specinfer, names", [
    (dict(prefix_caching=True), 1, False, "prefix_caching"),
    ({}, 1, True, "SpecInfer or beam search"),
    (dict(kv_quant="int8"), 1, False, "kv_quant"),
    (dict(fused_decode=("rope_kv_write",)), 1, False, "fused_decode"),
    (dict(kv_shard="context", context_shards=2), 1, False, "kv_shard"),
    (dict(kv_layout="dense"), 1, False, "kv_layout"),
    ({}, 2, False, "model > 1"),
], ids=["prefix_caching", "specinfer", "kv_quant", "fused_decode", "kv_shard",
        "dense", "model"])
def test_the_seven_refusals_name_their_reason(
        tiny, serving, model, specinfer, names, tiny_servers):
    """``validate_serving``, as the engine calls it at construction."""
    from flexflow_tpu.core.mesh import MachineSpec

    cfg, params = tiny
    mesh = MachineSpec(model=model).make_mesh(jax.devices()[:model])
    with pytest.raises(NotImplementedError, match=f"qwen3_next does not serve.*{names}"):
        fam.validate_serving(cfg, tiny_servers.serving(**serving), mesh, specinfer=specinfer)
    if not specinfer:  # and the engine does call it
        with pytest.raises((NotImplementedError, ValueError),
                           match="qwen3_next does not|does not advertise"):
            InferenceEngine(fam, cfg, params, tiny_servers.serving(**serving), mesh)

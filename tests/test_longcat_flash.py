"""LongCat-Flash on the paged serving path (models/longcat_flash.py)
against its plain reference (benchmarks/references/longcat_flash.py,
the one copy; imported by path), at a tiny size on the CPU in float32
with the family's own seeded weights (a non-zero selection offset), a
float32 latent pool of TWO lines a token and layer, pages of 16 (conftest.py).

Tolerances, each with its reason. LOGITS: rms(served - reference) /
rms(reference) under 2e-5 a judged row, DeepSeek's tests' limit: sound
float32 reads 1.9e-7 here (the absorbed form sums in another order
than the expanded one); with the factor on ``c`` left out the same
rows read 1.0e-1, with the factor on ``q`` left out 2.5e-3, with the
chosen weights renormalised 5.0e-2, without the scaling of 6 8.8e-2,
with the rope table at theta 1e4 1.7e-3 (my CPU readings, PR 60), so
each fails by two orders or more. TOKENS: greedy
tokens through ``RequestManager`` are the reference's argmax at every
position (teacher-forced).
"""
import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import deepseek_v3
from flexflow_tpu.models import longcat_flash as fam
from flexflow_tpu.serve.engine import InferenceEngine
from flexflow_tpu.serve.llm import LLM

from family_cases import *  # noqa: F401,F403 (the cases every family answers)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGITS_LIMIT = 2e-5
PAGE, CHUNK = 16, 16            # the tiny serving configuration's (conftest.py)
# two attentions and two dense FFNs a layer, the routed block (its
# identity outputs' part too) on a shortcut across the second pair
FAMILIES = {"longcat_flash": Family(fam, ALWAYS | {"ff.moe.route"})}

# the catalog's row (model-configs guide, architectures.jsonl), its
# ``config`` verbatim
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers": 28,
    "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 512,
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
# LongCat-Flash-Lite's row: the same keys, an n-gram embedding and YaRN
LITE = dict(PUBLISHED, hidden_size=3072, ffn_hidden_size=6144,
            expert_ffn_hidden_size=1024, num_layers=14, num_attention_heads=32,
            n_routed_experts=256, rope_theta=5000000,
            max_position_embeddings=327680, zero_expert_num=128,
            rope_scaling={"original_max_position_embeddings": 32768,
                          "rope_type": "yarn", "factor": 10, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
            ngram_vocab_size_ratio=78, emb_neighbor_num=4, emb_split_num=4)
del LITE["attention_method"]


def _reference():
    path = os.path.join(ROOT, "benchmarks", "references", "longcat_flash.py")
    spec = importlib.util.spec_from_file_location("reference_longcat_flash", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _reference()


def _file_config(cfg, **kw):
    """The tiny preset as a configuration FILE's keys: what the
    reference reads."""
    lo, hi = cfg.held
    d = dict(
        hidden_size=cfg.hidden_size, num_layers=cfg.num_hidden_layers,
        rms_norm_eps=cfg.norm_eps, num_attention_heads=cfg.num_attention_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        mla_scale_q_lora=True, mla_scale_kv_lora=True,
        n_routed_experts=hi - lo, router_outputs=cfg.router_outputs,
        experts_held=[lo, hi], zero_expert_num=cfg.zero_expert_num,
        moe_topk=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rope_theta=cfg.rope_theta, tolerance={"routing_margin": 0.05})
    d.update(kw)
    return d


@pytest.fixture(scope="module")
def tiny(tiny_servers):
    return tiny_servers.params(fam)


@pytest.fixture
def shared(tiny_servers):
    return tiny_servers(fam).llm


def _release(eng):
    for r in range(eng.num_slots):
        eng.pager.release(r)


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


# --- 1. the served path against the reference ---------------------------------


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_served_logits_match_the_reference(tiny, tiny_servers, kernels):
    """Chunked prefill of one row (a ragged last chunk), mixed steps in
    which it decodes while another prefills (packed rungs of the
    ladder), then pure decode steps, through the latent pool with two
    lines a layer: every row the server would sample from, against the
    reference's full forward pass in the expanded form; the step's
    counters are the pairs of its real tokens."""
    cfg, params = tiny
    eng = tiny_servers(fam, kernels=kernels).engine
    assert eng.pack_ladder(CHUNK) == (16, 32)
    assert eng.cache["latent"].shape[0] == 2 * cfg.num_hidden_layers
    rng = np.random.default_rng(1)
    seqs = {r: rng.integers(0, cfg.vocab_size, 70).tolist() for r in (0, 2)}
    judged, done = {}, {0: 0, 2: 0}

    def step(chunk, feed):
        rows = {r: (seqs[r][done[r]:done[r] + n], done[r]) for r, n in feed.items()}
        logits = _feed(eng, rows, chunk)
        counts = eng.split_fetch(np.asarray(eng.step_fetch))[1]
        pairs = sum(feed.values()) * cfg.num_experts_per_tok
        assert counts["moe_counts"].shape == (cfg.num_hidden_layers, 16)
        assert (counts["moe_routed_pairs"] == pairs).all()
        # every expert is held here: a pair is an expert's or an identity output's
        assert (counts["moe_counts"].sum(-1) + counts["moe_zero_pairs"] == pairs).all()
        assert counts["moe_zero_pairs"].sum() > 0
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
    _release(eng)
    worst = max(_rms_share(got, want[r // 2, t]) for (r, t), got in judged.items())
    assert len(judged) == 3 + 2 * 3 + 2 * 4 and worst < LOGITS_LIMIT, worst


def test_greedy_tokens_through_generate_are_the_references(tiny, shared):
    cfg, params = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (21, 40, 9)]
    before = dataclasses.replace(shared.rm.stats)
    outs = shared.generate(prompts, max_new_tokens=6)
    for prompt, out in zip(prompts, outs):
        full = prompt + out.output_tokens
        want = reference.forward(params, _file_config(cfg), np.asarray([full]))[0]
        assert out.output_tokens == want[len(prompt) - 1:-1].argmax(-1).tolist()
    stats = shared.rm.stats
    tokens = sum(map(len, prompts)) + 3 * 5
    layers = cfg.num_hidden_layers
    # every real token wrote TWO lines a layer and chose k outputs a layer
    assert stats.latent_lines - before.latent_lines == tokens * 2 * layers
    routed = stats.moe_routed_pairs - before.moe_routed_pairs
    assert routed == tokens * cfg.num_experts_per_tok * layers
    assert (stats.moe_pairs - before.moe_pairs
            + stats.moe_zero_pairs - before.moe_zero_pairs) == routed
    assert stats.decode_context_lines > before.decode_context_lines
    assert stats.slot_state_bytes == 0


def test_the_engine_counts_two_lines_a_layer_from_the_family_arrays(shared):
    eng = shared.engine
    cfg = eng.cfg
    per_line = 2 * cfg.num_hidden_layers * cfg.line_dim * 4    # float32 pool
    assert eng.kv_bytes_per_line() == per_line
    assert eng.pager.ensure(0, 20)
    assert eng.kv_allocated_bytes() == 2 * PAGE * per_line
    eng.pager.release(0)
    # the published widths in bf16: 2 x 1152 B a token and layer
    big = fam.config(num_hidden_layers=4)
    cache = jax.eval_shape(lambda: fam.init_paged_kv_cache(big, 6, 128))
    total = sum(math.prod(a.shape) * a.dtype.itemsize for a in cache.values())
    assert total / (7 * 128) == 2 * 1152 * 4 == 9216
    assert all(a.shape[-1] % 128 == 0 for a in cache.values())  # whole lane tiles


# --- 2. the topology -----------------------------------------------------------


def _one_layer(seed=4, **zeroed):
    """A one-layer model; ``zeroed``: group -> the leaf written as zeros."""
    cfg = fam.tiny(dtype=jnp.float32, num_hidden_layers=1)
    params = fam.init_params(jax.random.PRNGKey(seed), cfg)
    for group, leaf in zeroed.items():
        params[group] = dict(params[group], **{
            leaf: jnp.zeros_like(params[group][leaf])})
    return cfg, params


def _prefill(cfg, params, tokens, capture=None):
    """One unpacked prefill step of ``tokens`` in row 0, eagerly where
    ``capture`` wants the routed block's operands: (logits (V,), cache)."""
    T = len(tokens)
    cache = fam.init_paged_kv_cache(cfg, 8, 4, jnp.float32)   # 8 pages of 4 lines
    table = jnp.arange(8, dtype=jnp.int32)[None]
    args = (params, cache, jnp.asarray([tokens], jnp.int32),
            jnp.arange(T, dtype=jnp.int32)[None], jnp.asarray([T - 1]), None,
            None, table)
    if capture is None:
        logits, cache = fam.serve_step_paged(*args, cfg=cfg, cache_len=31)
        return np.asarray(logits[0]), cache
    whole = fam.shortcut_moe

    def spy(cfg_, p, h, real, **kw):
        out = whole(cfg_, p, h, real, **kw)
        capture.append((np.asarray(h), np.asarray(out[0])))
        return out

    fam.shortcut_moe = spy
    try:
        with jax.disable_jit():
            logits, cache = fam.serve_step_paged(*args, cfg=cfg, cache_len=31)
    finally:
        fam.shortcut_moe = whole
    return np.asarray(logits[0]), cache


def test_the_shortcut_is_read_after_attn_0_and_added_after_ffn_1():
    """With ffn_0, attn_1 and ffn_1 writing zeros the layer leaves
    ``x + attn_0 + moe(norm_f0(.))``, composed here by hand from the
    reference's parts."""
    cfg, params = _one_layer(ffn0="w_down", mla1="wo", ffn1="w_down")
    tokens = np.random.default_rng(2).integers(0, cfg.vocab_size, 11).tolist()
    got, _ = _prefill(cfg, params, tokens)
    a = reference._sizes(_file_config(cfg))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0)
        x1, _ = reference._mla(x + 0.0, params["mla0"], 0, a, 0)
        h = reference._rmsnorm(x1, _layer_of(params, "ffn0")["mlp_norm_scale"], cfg.norm_eps)
        s = _ref_moe(h, _layer_of(params, "sparse"), dict(a))
        want = np.asarray(reference._head(params, _file_config(cfg), x1 + s))[-1]
    assert np.abs(np.asarray(s)).max() > 0.01 * np.abs(np.asarray(x1)).max()
    assert _rms_share(got, want) < LOGITS_LIMIT


def test_attn_1_does_not_reach_the_shortcut():
    """``s`` is a function of the row after attn_0 alone: other weights
    in attn_1 change the logits and leave ``h`` and ``s`` bit for bit."""
    cfg, params = _one_layer()
    other = dict(params, mla1=jax.tree.map(lambda w: w * 1.5, params["mla1"]))
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 4).tolist()
    seen_a, seen_b = [], []
    logits_a, _ = _prefill(cfg, params, tokens, seen_a)
    logits_b, _ = _prefill(cfg, other, tokens, seen_b)
    (h_a, s_a), (h_b, s_b) = seen_a[0], seen_b[0]
    np.testing.assert_array_equal(h_a, h_b)
    np.testing.assert_array_equal(s_a, s_b)
    assert _rms_share(logits_b, logits_a) > 1e-3


def test_sublayer_j_of_layer_i_writes_pool_index_2i_plus_j(tiny):
    """After one prefill the pool's entry 2 i + j holds the lines of
    layer i's j-th attention (the reference's, the factor on c
    included), and the scratch page nothing of them."""
    cfg, params = tiny
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size, 10).tolist()
    _, cache = _prefill(cfg, params, tokens)
    with jax.default_matmul_precision("highest"):
        _, lines, _ = reference._hidden(params, _file_config(cfg),
                                        jnp.asarray(tokens, jnp.int32))
    assert len(lines) == 2 * cfg.num_hidden_layers
    T = len(tokens)
    for index, (c, kr) in enumerate(lines):
        got = np.asarray(cache["latent"][index]).reshape(-1, cfg.kv_lora_rank)[:T]
        np.testing.assert_allclose(got, np.asarray(c), rtol=0, atol=2e-5)
        from flexflow_tpu.serve.kernels import unpair_rope_lines
        got = np.asarray(unpair_rope_lines(cache["latent_rope"][index]))
        np.testing.assert_allclose(got.reshape(-1, cfg.qk_rope_head_dim)[:T],
                                   np.asarray(kr), rtol=0, atol=2e-5)
    # two sublayers of a layer keep different lines
    assert np.abs(np.asarray(lines[0][0]) - np.asarray(lines[1][0])).max() > 0.1


# --- 3. the factors --------------------------------------------------------------


def test_the_two_factors_the_scale_and_the_rope_table():
    cfg = fam.config()
    assert cfg.mla_scale_q_lora == 2.0
    assert cfg.mla_scale_kv_lora == pytest.approx(math.sqrt(12), rel=1e-12)
    assert fam.softmax_scale(cfg) == 192 ** -0.5
    assert fam.config(mla_scale_q_lora=False).mla_scale_q_lora == 1.0
    pos = jnp.asarray([[0, 1, 77, 16000]])
    cos, sin = fam.rope_cos_sin(cfg, pos)
    inv = 1e7 ** -(np.arange(0, 64, 2) / 64)
    ang = np.asarray(pos)[..., None] * np.concatenate([inv, inv])
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-3)
    np.testing.assert_allclose(np.asarray(cos)[0, 1], np.cos(ang[0, 1]), atol=1e-6)
    # q carries its factor on nope and rope channels alike, c its own, kr none
    tiny = fam.tiny(dtype=jnp.float32)
    p = {k: v[0] for k, v in fam.init_params(jax.random.PRNGKey(1), tiny)["mla0"].items()}
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 5, tiny.hidden_size))
    rope = fam.rope_cos_sin(tiny, jnp.arange(5)[None])
    c, kr = deepseek_v3.latent_line(tiny, p, h, rope, tiny.mla_scale_kv_lora)
    c1, kr1 = deepseek_v3.latent_line(tiny, p, h, rope)
    np.testing.assert_allclose(np.asarray(c), 2.0 * np.asarray(c1), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(kr1))
    rms = np.sqrt(np.mean(np.asarray(c) ** 2, -1))
    np.testing.assert_allclose(rms, 2.0, rtol=1e-3)        # sqrt(64 / 16) x a unit norm
    q = deepseek_v3.absorbed_queries(tiny, p, h, rope, tiny.mla_scale_q_lora)
    q1 = deepseek_v3.absorbed_queries(tiny, p, h, rope)
    for scaled, plain in zip(q, q1):
        np.testing.assert_allclose(np.asarray(scaled),
                                   math.sqrt(64 / 24) * np.asarray(plain), rtol=1e-5)


# --- 4. the router and the identity outputs ---------------------------------------


def _layer_of(params, group, l=0):
    return {k: v[l] for k, v in params[group].items()}


def _ref_moe(h, p, a):
    """The reference's routed block under ONE layer's weights ``p``."""
    with jax.default_matmul_precision("highest"):
        return reference._moe(h, {k: v[None] for k, v in p.items()}, 0, False, a, 0)[0]


def test_the_router_softmax_offset_scaling_no_renormalisation(tiny):
    cfg, params = tiny
    p = _layer_of(params, "sparse")
    h = jax.random.normal(jax.random.PRNGKey(8), (33, cfg.hidden_size))
    outputs, weights = (np.asarray(a) for a in fam.route(cfg, p, h))
    logits = np.asarray(h, np.float64) @ np.asarray(p["w_router"], np.float64)
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)                     # over all 24 outputs
    assert prob.shape[-1] == 24
    t = prob + np.asarray(p["e_score_correction_bias"], np.float64)
    want = np.argsort(-t, axis=-1, kind="stable")[:, :4]
    np.testing.assert_array_equal(outputs, want)
    np.testing.assert_allclose(weights, 6 * np.take_along_axis(prob, want, -1), rtol=1e-5)
    assert np.all(weights.sum(-1) < 6 * 0.9)                # not renormalised
    # the offset moves the choice and not the weights
    moved = dict(p, e_score_correction_bias=p["e_score_correction_bias"].at[21].add(1.0))
    outputs2, weights2 = (np.asarray(a) for a in fam.route(cfg, moved, h))
    assert (outputs2[:, 0] == 21).all()
    np.testing.assert_allclose(weights2[:, 0], 6 * prob[:, 21], rtol=1e-5)
    assert (outputs != 21).any()


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_identity_outputs_cost_no_rows_and_return_their_input(tiny, kernels):
    cfg, params = tiny
    p = _layer_of(params, "sparse")
    h = jax.random.normal(jax.random.PRNGKey(9), (32, cfg.hidden_size))
    real = jnp.arange(32) < 29
    prob = np.asarray(jax.nn.softmax(h @ p["w_router"], axis=-1))
    up = lambda ids: dict(p, e_score_correction_bias=jnp.zeros(24).at[jnp.asarray(ids)].set(1.0))
    # every choice an identity output: 6 h sum(p), no group entered
    out, counts, zero, routed = fam.shortcut_moe(
        cfg, up([16, 18, 20, 23]), h, real, kernels=kernels)
    want = 6 * np.asarray(h) * prob[:, [16, 18, 20, 23]].sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out)[:29], want[:29], rtol=2e-5, atol=1e-6)
    assert not np.asarray(out)[29:].any() and not np.asarray(counts).any()
    assert (int(zero), int(routed)) == (4 * 29, 4 * 29)
    # no choice an identity output: the experts' sum alone
    chosen = [1, 5, 8, 14]
    out, counts, zero, routed = fam.shortcut_moe(cfg, up(chosen), h, real, kernels=kernels)
    with jax.default_matmul_precision("highest"):
        want = sum(6 * prob[:, e:e + 1] * np.asarray(reference._glu(h, p, (e,), 0))
                   for e in chosen)
    np.testing.assert_allclose(np.asarray(out)[:29], want[:29], rtol=0, atol=2e-5)
    assert int(zero) == 0 and np.asarray(counts)[chosen].tolist() == [29] * 4


def test_zero_held_and_absent_pairs_add_up_to_the_routed_pairs(tiny):
    cfg, params = tiny
    part = dataclasses.replace(cfg, experts_held=(4, 12))
    p = _layer_of(params, "sparse")
    p = dict(p, **{n: p[n][4:12] for n in ("w_gate", "w_up", "w_down")})
    h = jax.random.normal(jax.random.PRNGKey(10), (40, cfg.hidden_size))
    real = jnp.arange(40) % 5 != 0
    outputs = np.asarray(fam.route(part, p, h)[0])[np.asarray(real)]
    _, counts, zero, routed = fam.shortcut_moe(part, p, h, real)
    absent = int(((outputs < 4) | ((outputs >= 12) & (outputs < 16))).sum())
    assert int(zero) == int((outputs >= 16).sum()) > 0 and absent > 0
    assert int(zero) + int(np.asarray(counts).sum()) + absent == int(routed) == 4 * 32
    assert fam.expert_routing(part) == (4, (4, 12), 24)
    assert fam.step_counts(part) == {
        "moe_counts": (3, 8), "moe_zero_pairs": (3,), "moe_routed_pairs": (3,)}


# --- 5. the share ties to the model ----------------------------------------------


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_four_ranges_and_the_identity_part_once_add_up_to_the_uncut_block(tiny, kernels):
    cfg, params = tiny
    p = _layer_of(params, "sparse", 1)
    h = jax.random.normal(jax.random.PRNGKey(11), (48, cfg.hidden_size))
    real = jnp.ones(48, bool)
    a = dict(reference._sizes(_file_config(cfg)))
    whole = _ref_moe(h, p, a)
    with jax.default_matmul_precision("highest"):
        gate, _ = reference._route(h, p["w_router"], p["e_score_correction_bias"], False, a)
    identity = np.asarray(h) * np.asarray(gate)[:, 16:].sum(-1, keepdims=True)
    parts, pairs = [], 0
    for lo in range(0, 16, 4):
        cut = dataclasses.replace(cfg, experts_held=(lo, lo + 4))
        held = dict(p, **{n: p[n][lo:lo + 4] for n in ("w_gate", "w_up", "w_down")})
        out, counts, zero, routed = fam.shortcut_moe(cut, held, h, real, kernels=kernels)
        parts.append(np.asarray(out))
        pairs += int(np.asarray(counts).sum())
        # the reference, given the same share, leaves out the same
        ref_part = _ref_moe(h, held, dict(a, lo=lo, hi=lo + 4))
        np.testing.assert_allclose(parts[-1], np.asarray(ref_part), rtol=0, atol=2e-5)
    assert np.abs(identity).max() > 0.05 * np.abs(np.asarray(whole)).max()
    np.testing.assert_allclose(sum(parts) - 3 * identity, np.asarray(whole),
                               rtol=0, atol=5e-5)
    assert pairs + int(zero) == int(routed) == 4 * 48


# --- 6. counts, from_hf, refusals --------------------------------------------------


def test_the_published_config_counts_560_b_and_its_two_active_counts():
    cfg = fam.from_hf(PUBLISHED, dtype=jnp.bfloat16)
    assert cfg == fam.config(dtype=jnp.bfloat16)        # the family's defaults ARE the row
    assert (cfg.router_outputs, cfg.held, cfg.head_dim, cfg.line_dim) == (
        768, (0, 512), 192, 576)
    assert fam.num_params(cfg) / 1e9 == pytest.approx(560.66, rel=1e-3)
    assert fam.active_params(cfg, 0) / 1e9 == pytest.approx(18.69, rel=1e-3)
    assert fam.active_params(cfg, 12) / 1e9 == pytest.approx(31.37, rel=1e-3)
    shapes = jax.eval_shape(lambda: fam.init_params(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(math.prod(a.shape) for a in jax.tree.leaves(tree)) / 28
    assert count(shapes["mla0"]) / 1e6 == pytest.approx(90.57, rel=1e-3)
    assert count(shapes["ffn1"]) / 1e6 == pytest.approx(226.49, rel=1e-3)
    assert math.prod(shapes["sparse"]["w_router"].shape) / 28 / 1e6 == pytest.approx(
        4.72, rel=1e-3)
    assert count(shapes["sparse"]) / 1e9 == pytest.approx(19.33 + 0.0047, rel=1e-3)


def test_from_hf_reads_the_benchmark_configuration():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs", "longcat-flash-chat.json")) as f:
        hf = json.load(f)
    for key, value in PUBLISHED.items():                # every width as published
        if key not in hf["reduced"]:
            assert hf[key] == value, key
    assert set(hf["reduced"]) == {"num_layers", "n_routed_experts", "vocab_size"}
    assert "n_group" not in hf
    cut = fam.from_hf(hf, dtype=jnp.bfloat16)
    assert (cut.num_hidden_layers, cut.n_routed_experts, cut.held, cut.vocab_size) == (
        4, 512, (0, 16), 16384)
    assert (cut.router_outputs, cut.num_experts_per_tok) == (768, 12)
    # 4 x 1242.8 M + 201.3 M: 5172.6 M parameters, 10.35 GB
    assert fam.num_params(cut) / 1e6 == pytest.approx(5172.6, abs=0.5)
    shapes = jax.eval_shape(lambda: fam.init_params(jax.random.PRNGKey(0), cut))
    names = {path[-1].key for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    # harness/model.py zeroes a leaf whose name holds "bias": the offset alone
    assert [n for n in names if "bias" in n or n[0] == "b"] == ["e_score_correction_bias"]
    assert fam.step_counts(cut)["moe_counts"] == (4, 16)


@pytest.mark.parametrize("row, names", [
    (LITE, "ngram_vocab_size_ratio"),
    (dict(PUBLISHED, attention_method="MHA"), "attention_method"),
    (dict(PUBLISHED, zero_expert_type="zero"), "zero_expert_type"),
    (dict(PUBLISHED, attention_bias=True), "attention_bias"),
    (dict(PUBLISHED, rope_scaling={"rope_type": "yarn", "factor": 10}), "rope_scaling"),
    (dict(PUBLISHED, hidden_act="gelu"), "hidden_act"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_from_hf_refuses_by_name_what_nothing_builds(row, names):
    with pytest.raises(NotImplementedError, match=f"longcat_flash.*{names}"):
        fam.from_hf(row)


def test_a_share_that_is_not_the_count_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        fam.from_hf(dict(PUBLISHED, n_routed_experts=16, router_outputs=768,
                         experts_held=[0, 8]))
    with pytest.raises(ValueError, match="experts_held"):
        fam.tiny(experts_held=(8, 20))


@pytest.mark.parametrize("serving, names", [
    (dict(prefix_caching=True), "longcat_flash.*prefix_caching"),
    (dict(kv_quant="int8"), "longcat_flash.*kv_quant"),
    (dict(fused_decode=("rope_kv_write",)), "rope_kv_write"),
    (dict(kv_shard="context", context_shards=2), "kv_shard"),
    (dict(kv_layout="dense"), "longcat_flash.*kv_layout"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_refused_combinations_name_their_reason(tiny, tiny_servers, serving, names):
    cfg, params = tiny
    with pytest.raises((NotImplementedError, ValueError), match=names):
        InferenceEngine(fam, cfg, params, tiny_servers.serving(**serving))


def test_a_model_parallel_mesh_is_refused(tiny, tiny_servers):
    from flexflow_tpu.core.mesh import MachineSpec

    cfg, params = tiny
    mesh = MachineSpec(model=2).make_mesh(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="longcat_flash.*model > 1"):
        InferenceEngine(fam, cfg, params, tiny_servers.serving(), mesh)


def test_speculation_and_beam_search_are_refused(tiny, shared, tiny_servers):
    from flexflow_tpu.serve import GenerationConfig, SpecConfig

    cfg, params = tiny
    llm = LLM(fam, cfg, params=params)
    with pytest.raises(NotImplementedError, match="longcat_flash.*SpecInfer"):
        llm.compile(tiny_servers.serving(), spec=SpecConfig(draft="early_exit", draft_layers=1))
    with pytest.raises(NotImplementedError, match="latent page pool"):
        shared.generate([[1, 2, 3]], GenerationConfig(num_beams=2, max_new_tokens=2))


# --- the reference's own rule -------------------------------------------------------


def test_the_reference_bounds_its_routings(tiny):
    """At most 2^4 routings a judged token; routing 0 is float32's own
    and is the full forward pass; a routing flips only layers that
    count (one of the two outputs held or identity) under the margin."""
    cfg, params = tiny
    file_cfg = _file_config(cfg, tolerance={"routing_margin": 0.2})
    tokens = np.random.default_rng(12).integers(0, cfg.vocab_size, (1, 24))
    judge = np.asarray([[3, 11, 23]])
    logits, flip_margin, margin = reference.judged_logits(params, file_cfg, tokens, judge)
    assert logits.shape == (1, 3, 8, cfg.vocab_size)       # 2^min(4, 3 layers)
    want = reference.forward(params, file_cfg, tokens)
    np.testing.assert_allclose(logits[0, :, 0], want[0, judge[0]], rtol=0, atol=1e-5)
    assert (flip_margin[..., 0] == 0).all()
    finite = np.isfinite(flip_margin)
    assert finite[..., 1:].any() and (flip_margin[finite] <= 0.2).all()
    assert (margin <= flip_margin[..., 1:].min(-1) + 1e-6).all()
    # a held range that neither output of a tight layer touches: the layer no longer counts
    none = reference.judged_logits(params, _file_config(
        dataclasses.replace(cfg, zero_expert_num=0, n_routed_experts=24,
                            experts_held=(0, 1)),
        tolerance={"routing_margin": 0.2}), tokens, judge)
    assert np.isfinite(none[1]).sum() <= finite.sum()
    # the control changes the numbers and not the shapes
    control = reference.judged_logits(params, file_cfg, tokens, judge, control_bits=8)
    assert control[0].shape == (1, 3, 1, cfg.vocab_size)
    assert 1e-4 < _rms_share(control[0][0, :, 0], logits[0, :, 0]) < 0.1
    flips, valid = reference.flipped_layers(
        np.asarray([[0.01, np.inf, 0.3], [np.inf, np.inf, np.inf]]), 0.2)
    assert valid.sum(-1).tolist() == [2, 1] and flips[0, 1].tolist() == [True, False, False]


@pytest.mark.parametrize("C,ps", [(8, 4), (8, 16), (1, 16), (16, 16)])
def test_rope_keys_are_written_by_whole_pages(C, ps):
    """``kernels.write_rope_lines`` against a token-by-token write of the
    unpaired lines: rows that start inside a page and run over several,
    a row of one token (a decode row), a row with none, both halves of
    a page; every other line of the pool, the scratch page's too, stays
    what it was."""
    from flexflow_tpu.serve import kernels

    r, R, NP, P, line = 8, 4, 6, 24, 3
    pool = jax.random.normal(jax.random.PRNGKey(0), (5, P + 1, ps // 2, 2 * r))
    table = jnp.asarray(np.random.RandomState(0).permutation(P)
                        .reshape(R, NP).astype(np.int32))
    start = jnp.asarray([ps - 1, 2 * ps + ps // 2, 0, 5], jnp.int32)
    count = jnp.asarray([C, 1, 0, max(C - 1, 1)], jnp.int32)
    kr = jax.random.normal(jax.random.PRNGKey(1), (R, C, r))
    got = jax.jit(kernels.write_rope_lines)(
        pool, jnp.int32(line), kr, table, start, count)
    want = np.asarray(kernels.unpair_rope_lines(pool)).copy()
    for i in range(R):
        for j in range(int(count[i])):
            pos = int(start[i]) + j
            want[line, int(table[i, pos // ps]), pos % ps] = np.asarray(kr[i, j])
    np.testing.assert_array_equal(
        np.asarray(kernels.unpair_rope_lines(got)), want)

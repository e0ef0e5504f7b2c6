"""DeepSeek-V3 on the paged serving path (models/deepseek_v3.py) against
its plain reference (benchmarks/references/deepseek_v3.py, the one
copy; imported by path), at a tiny size on the CPU in float32 with the
family's own seeded weights (a non-zero selection offset), a float32
latent pool.

Tolerances, each with its reason. LOGITS: rms(served - reference) /
rms(reference) under 2e-5 a judged row. Sound float32 reads 2e-7 (the
absorbed form sums in another order than the expanded one); with the
rope key left unrotated the same rows read 3.0e-3, with the softmax
scale without its mscale^2 2.4e-3, with the group choice left out (the
4 largest of all 16) 4.3e-2 (my CPU readings, PR 40), so each fails by
two orders. TOKENS: greedy tokens through ``RequestManager`` are the
reference's argmax at every position (teacher-forced).
"""
import dataclasses
import importlib.util
import itertools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import deepseek_v3 as fam
from flexflow_tpu.models import transformer
from flexflow_tpu.serve import kernels
from flexflow_tpu.serve.engine import InferenceEngine
from flexflow_tpu.serve.llm import LLM

from family_cases import *  # noqa: F401,F403 (the cases every family answers)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGITS_LIMIT = 2e-5
PAGE, CHUNK = 16, 16            # the tiny serving configuration's (conftest.py)
FAMILIES = {"deepseek_v3": Family(fam, ALWAYS | {"ff.moe.route"})}


def _reference():
    path = os.path.join(ROOT, "benchmarks", "references", "deepseek_v3.py")
    spec = importlib.util.spec_from_file_location("reference_deepseek_v3", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _reference()


def _file_config(cfg, **kw):
    """The tiny preset as a configuration FILE's keys: what the
    reference reads."""
    lo, hi = cfg.held
    d = dict(
        num_hidden_layers=cfg.num_hidden_layers,
        first_k_dense_replace=cfg.first_k_dense_replace,
        rms_norm_eps=cfg.norm_eps, num_attention_heads=cfg.num_attention_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        n_routed_experts=hi - lo, router_outputs=cfg.n_routed_experts,
        experts_held=[lo, hi], n_group=cfg.n_group, topk_group=cfg.topk_group,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.moe_norm_topk,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rope_theta=cfg.rope_theta,
        max_position_embeddings=cfg.max_position_embeddings,
        rope_scaling=dict(
            type="yarn", factor=cfg.rope_factor,
            original_max_position_embeddings=cfg.rope_original_max,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
            mscale=cfg.rope_mscale, mscale_all_dim=cfg.rope_mscale_all_dim),
        tolerance={"routing_margin": 0.05})
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


# --- (a) the served path against the reference ------------------------------


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_served_logits_match_the_reference(tiny, tiny_servers, kernels):
    """Chunked prefill of one row (a ragged last chunk), mixed steps in
    which it decodes while another prefills (packed rungs of the
    ladder), then pure decode steps, through the latent paged pool:
    every row the server would sample from, against the reference's
    full forward pass in the expanded form; the step's expert counts
    are the routed pairs of its real tokens."""
    cfg, params = tiny
    eng = tiny_servers(fam, kernels=kernels).engine
    assert eng.pack_ladder(CHUNK) == (16, 32)
    rng = np.random.default_rng(1)
    seqs = {r: rng.integers(0, cfg.vocab_size, 70).tolist() for r in (0, 2)}
    judged, done = {}, {0: 0, 2: 0}

    def step(chunk, feed):
        rows = {r: (seqs[r][done[r]:done[r] + n], done[r]) for r, n in feed.items()}
        logits = _feed(eng, rows, chunk)
        counts = eng.split_fetch(np.asarray(eng.step_fetch))[1]["moe_counts"]
        assert counts.shape == (cfg.count("sparse"), cfg.n_routed_experts)
        assert (counts.sum(-1) == sum(feed.values()) * cfg.num_experts_per_tok).all()
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
    # every real token wrote one line a layer, and routed k pairs a sparse layer
    assert stats.latent_lines - before.latent_lines == tokens * cfg.num_hidden_layers
    assert stats.moe_pairs - before.moe_pairs == (
        tokens * cfg.num_experts_per_tok * cfg.count("sparse"))
    assert stats.slot_state_bytes == 0


def test_a_preempted_request_recomputes_to_the_same_tokens(tiny, shared, tiny_servers):
    cfg, _ = tiny
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, 40 + 8 * i).tolist() for i in range(4)]
    want = [o.output_tokens for o in shared.generate(prompts, max_new_tokens=8)]
    tight = tiny_servers(fam, fresh=True, max_sequence_length=96, max_cached_tokens=128).llm
    outs = tight.generate(prompts, max_new_tokens=8)
    assert [o.output_tokens for o in outs] == want
    assert tight.rm.stats.preemptions > 0, "the pool was never oversubscribed"
    tight.engine.pager.check_no_leaks()


# --- (b) the absorbed form is the expanded form ------------------------------


def _lines_in_pages(c, kr, page):
    """Token lines (S, .) as the family's two pool arrays, pages in
    order, plus the scratch page."""
    S = c.shape[0]
    cp = c.reshape(S // page, page, -1)
    half = kr.reshape(S // page, 2, page // 2, kr.shape[-1])
    krp = jnp.concatenate([half[:, 0], half[:, 1]], axis=-1)
    pad = lambda a: jnp.concatenate([a, jnp.zeros_like(a[:1])])
    return pad(cp), pad(krp)


@pytest.mark.parametrize("twin", ["xla", "pallas"])
def test_absorbed_attention_over_the_pool_is_expanded_attention(twin):
    """One layer's attention for 40 tokens: the reference's expanded
    form (a key and a value a head from ``c W_kvb``) against the
    absorbed queries on the latent lines through the paged kernel and
    its XLA twin, rows at several depths and a padding row."""
    cfg = fam.tiny(dtype=jnp.float32)
    params = fam.init_params(jax.random.PRNGKey(2), cfg)
    p = {k: v[1] for k, v in params["mla"].items()}
    file_cfg = _file_config(cfg)
    a = reference._sizes(file_cfg)
    T, page = 48, 8
    x = jax.random.normal(jax.random.PRNGKey(3), (T, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference._mla(x, p, a, 0) - x)
    pos = jnp.arange(T)[None]
    h = transformer._norm(cfg, x[None], p["attn_norm_scale"], None)
    rope = fam.rope_cos_sin(cfg, pos)
    c, kr = fam.latent_line(cfg, p, h, rope)
    cp, krp = _lines_in_pages(c[0], kr[0], page)
    np.testing.assert_array_equal(
        np.asarray(kernels.unpair_rope_lines(krp[:-1]).reshape(T, -1)), np.asarray(kr[0]))
    q_abs, q_rope = fam.absorbed_queries(cfg, p, h, rope)
    # rows: 8 queries from position 40; 5 from 3; one (a decode row) at 17; padding
    starts, lens = [40, 3, 17, 0], [8, 5, 1, 0]
    C = 8
    take = lambda q: jnp.stack([jnp.pad(q[0, s:s + n], ((0, C - n), (0, 0), (0, 0)))
                                for s, n in zip(starts, lens)])
    table = jnp.tile(jnp.arange(T // page, dtype=jnp.int32)[None], (4, 1))
    fn = kernels.mla_paged_attention if twin == "pallas" else kernels.mla_paged_attention_xla
    o = fn(take(q_abs), take(q_rope), cp, krp, table, jnp.asarray(starts, jnp.int32),
           jnp.asarray(lens, jnp.int32), scale=fam.softmax_scale(cfg))
    _, w_uv = fam.kv_up_halves(cfg, p["w_kvb"])
    out = jnp.einsum("rqhc,chd->rqhd", o, w_uv).reshape(4, C, -1) @ p["wo"]
    for r, (s, n) in enumerate(zip(starts, lens)):
        np.testing.assert_allclose(np.asarray(out[r, :n]), want[s:s + n], rtol=0,
                                   atol=2e-5 * np.abs(want).max())
        assert not np.asarray(o[r, n:]).any()      # padding columns come out zero


# the block of pages a grid step attends (kernels.mla_block): each case
# gives rows as (last position, real queries as a share of the chunk),
# in units of the block's KB pages of LINES lines
LINES, HEADS, LINE, ROPE = 16, 2, 256, 8
KERNEL_CASES = {
    # the table's last block holds 3 pages of KB; a row ends in it
    "pages_no_multiple_of_the_block": (lambda kb: 2 * kb + 3, [
        (lambda kb: (2 * kb + 2) * LINES + 5, 1.0), (lambda kb: 2 * kb * LINES, 1.0),
        (lambda kb: kb * LINES + 3, 0.5), (lambda kb: 7, 0.0)]),
    "last_query_in_the_first_page_of_a_block": (lambda kb: 3 * kb, [
        (lambda kb: kb * LINES + 2, 1.0), (lambda kb: 2 * kb * LINES, 1.0),
        (lambda kb: kb * LINES + LINES - 1, 0.5), (lambda kb: kb * LINES, 0.5)]),
    "last_query_in_the_last_page_of_a_block": (lambda kb: 3 * kb, [
        (lambda kb: 2 * kb * LINES - 1, 1.0), (lambda kb: 3 * kb * LINES - 2, 1.0),
        (lambda kb: 2 * kb * LINES - LINES, 0.5), (lambda kb: kb * LINES - 3, 1.0)]),
    "a_context_of_exactly_one_block": (lambda kb: 2 * kb, [
        (lambda kb: kb * LINES - 1, 1.0), (lambda kb: kb * LINES - 1, 0.5),
        (lambda kb: kb * LINES, 1.0), (lambda kb: kb * LINES - 1, 0.0)]),
    "a_decode_row_beside_prefilling_rows": (lambda kb: 2 * kb + 1, [
        (lambda kb: kb * LINES + 40, 1.0), (lambda kb: 2 * kb * LINES + 9, 0.01),
        (lambda kb: 31, 1.0), (lambda kb: kb * LINES - 1, 0.01)]),
    "padding_rows": (lambda kb: kb + 2, [
        (lambda kb: 0, 0.0), (lambda kb: kb * LINES + 20, 1.0),
        (lambda kb: 0, 0.0), (lambda kb: 0, 0.0)]),
}


@pytest.mark.parametrize("C", [1, 8, 32])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_latent_kernel_by_blocks_is_its_twin(case, C):
    """``mla_paged_attention`` (interpret mode) against
    ``mla_paged_attention_xla`` in float32 where the block of pages a
    grid step attends matters: at C = 32 a row's two query tiles walk
    different block counts; the pools are three layers' pages and the
    table's entry 0 is the second layer's first row."""
    pages, rows = KERNEL_CASES[case]
    _, kb = kernels.mla_block(C, 64, HEADS, LINES)
    NP, layers, R = pages(kb), 3, len(rows)
    assert kb > 1 and NP >= kb
    lens = [min(C, max(1, math.ceil(share * C))) if share else 0 for _, share in rows]
    starts = [max(last(kb) - n + 1, 0) for (last, _), n in zip(rows, lens)]
    assert all(s + n <= NP * LINES for s, n in zip(starts, lens))
    per = R * NP + 1                                # a layer's rows, its scratch page last
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    q_abs = jax.random.normal(k[0], (R, C, HEADS, LINE))
    q_rope = jax.random.normal(k[1], (R, C, HEADS, ROPE))
    c_pool = jax.random.normal(k[2], (layers * per, LINES, LINE))
    kr_pool = jax.random.normal(k[3], (layers * per, LINES // 2, 2 * ROPE))
    table = jnp.asarray(np.random.default_rng(9).permutation(R * NP).reshape(R, NP), jnp.int32)
    args = (table, jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32))
    got = kernels.mla_paged_attention(q_abs, q_rope, c_pool, kr_pool, *args, scale=0.07,
                                      row_offset=jnp.int32(per))
    want = kernels.mla_paged_attention_xla(q_abs, q_rope, c_pool[per:2 * per],
                                           kr_pool[per:2 * per], *args, scale=0.07)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-5)
    for r, n in enumerate(lens):
        assert not np.asarray(got[r, n:]).any()     # padding columns come out zero
        assert n == 0 or np.asarray(got[r, :n]).any()


# --- (c) the choice by groups -------------------------------------------------


def _brute_force(t, s, k, n_group, topk_group, scaling):
    """The group-limited choice by enumeration, one token: every set of
    ``topk_group`` groups, the one with the largest total of group
    scores (a group's score: its two largest t), ties to the set that
    comes first in index order; then the k largest t among its experts,
    ties to the lower index."""
    E = len(t)
    per = E // n_group
    score = [sum(sorted(t[g * per:(g + 1) * per], reverse=True)[:2]) for g in range(n_group)]
    best = max(itertools.combinations(range(n_group), topk_group),
               key=lambda gs: (sum(np.float32(score[g]) for g in gs), [-g for g in gs]))
    inside = [e for e in range(E) if e // per in best]
    chosen = sorted(inside, key=lambda e: (-t[e], e))[:k]
    total = sum(s[e] for e in chosen) + 1e-20
    return chosen, [scaling * s[e] / total for e in chosen]


@pytest.mark.parametrize("case", ["random", "ties", "dominant_offset"])
def test_the_choice_by_groups_is_the_brute_force_enumeration(case):
    E, n_group, topk_group, k = 32, 8, 4, 6
    rng = np.random.default_rng(11)
    T = 64
    logits = rng.standard_normal((T, E)).astype(np.float32)
    offset = (rng.standard_normal(E) * 0.1).astype(np.float32)
    if case == "ties":     # few distinct scores: level experts and level groups
        logits = rng.integers(-1, 2, (T, E)).astype(np.float32)
        offset = np.zeros(E, np.float32)
    if case == "dominant_offset":   # the offset alone decides the groups
        offset = np.repeat(rng.permutation(n_group), E // n_group).astype(np.float32) * 3
    h = jnp.asarray(logits)
    experts, weights = transformer.route_sigmoid_topk(
        h, jnp.eye(E), jnp.asarray(offset), k, scaling=2.5,
        groups=(n_group, topk_group), eps=1e-20)
    s = np.asarray(jax.nn.sigmoid(h))
    t = s + offset
    for i in range(T):
        chosen, w = _brute_force(t[i].tolist(), s[i].tolist(), k, n_group, topk_group, 2.5)
        assert np.asarray(experts[i]).tolist() == chosen, (case, i)
        np.testing.assert_allclose(np.asarray(weights[i]), w, rtol=2e-6)
    if case == "dominant_offset":   # nothing is chosen outside the offset's four best groups
        kept = set(np.argsort(-offset[::E // n_group])[:topk_group].tolist())
        assert set((np.asarray(experts).reshape(-1) // (E // n_group)).tolist()) <= kept
    # the reference's router makes the same choice
    gate, margin = reference._route(
        h, jnp.eye(E), jnp.asarray(offset), jnp.asarray(False), k=k,
        n_group=n_group, topk_group=topk_group, norm=True, scaling=2.5)
    assert margin.shape == (T, 2)       # the groups' choice, the experts'
    want = np.zeros((T, E), np.float32)
    np.put_along_axis(want, np.asarray(experts), np.asarray(weights), axis=1)
    np.testing.assert_allclose(np.asarray(gate), want, rtol=2e-6, atol=1e-7)
    assert (np.asarray(margin) >= 0).all()
    if case == "ties":
        assert (np.asarray(margin) == 0).any()


def test_one_group_is_the_router_as_it_was():
    """LFM2's call (no ``groups``, eps 1e-6) traces as before the
    argument existed: no group arithmetic in its jaxpr."""
    h = jnp.ones((3, 8))
    w = jnp.ones((8, 16))
    text = str(jax.make_jaxpr(
        lambda h, w, o: transformer.route_sigmoid_topk(h, w, o, 4))(h, w, jnp.zeros(16)))
    assert text.count("top_k") == 1 and "1e-06" in text.replace("9.999999974752427e-07", "1e-06")


# --- (d) YaRN and the softmax scale, by hand ---------------------------------


def test_yarn_frequencies_and_softmax_scale_against_hand_values():
    cfg = fam.config(num_hidden_layers=1, dtype=jnp.float32)
    inv = fam.yarn_inv_freq(cfg)
    plain = 10000.0 ** -(np.arange(32) / 32.0)
    # ch(32) = 64 ln(4096 / (64 pi)) / (2 ln 1e4) = 10.47 -> 10
    # ch(1) = 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> 23
    assert math.floor(64 * math.log(4096 / (64 * math.pi)) / (2 * math.log(1e4))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4))) == 23
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-12)        # untouched
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-12)   # over factor
    # channel 16: ramp (16 - 10) / 13, between the two
    r = 6 / 13
    np.testing.assert_allclose(inv[16], plain[16] * (1 - r) + plain[16] / 40 * r, rtol=1e-12)
    np.testing.assert_allclose(inv, reference._sizes(_file_config(cfg))["inv_freq"], rtol=1e-12)
    m = 0.1 * math.log(40) + 1
    assert abs(m - 1.3689) < 1e-4
    assert abs(fam.softmax_scale(cfg) - 192 ** -0.5 * m * m) < 1e-12
    assert abs(fam.softmax_scale(cfg) - 0.13524) < 1e-5
    cos, sin = fam.rope_cos_sin(cfg, jnp.asarray([[0, 5]]))
    assert cos.shape == (1, 2, 64)      # unscaled: mscale / mscale_all_dim = 1
    np.testing.assert_allclose(np.asarray(cos[0, 1, :32]), np.cos(5 * inv), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin[0, 1, 32:]), np.sin(5 * inv), atol=1e-6)


# --- (e) the share ------------------------------------------------------------


def _sparse_layer(D=32, F=16, T=40, seed=9):
    cfg = fam.tiny(dtype=jnp.float32, n_routed_experts=64, n_group=8,
                   topk_group=4, num_experts_per_tok=8, hidden_size=D,
                   moe_intermediate_size=F)
    E = 64
    key = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    p = {"w_router": jax.random.normal(next(key), (D, E)) * 0.5,
         "router_offset": jax.random.normal(next(key), (E,)) * 0.1,
         "w_gate": jax.random.normal(next(key), (E, D, F)) * 0.2,
         "w_up": jax.random.normal(next(key), (E, D, F)) * 0.2,
         "w_down": jax.random.normal(next(key), (E, F, D)) * 0.2,
         "shared": {"w_gate": jax.random.normal(next(key), (D, F)) * 0.2,
                    "w_up": jax.random.normal(next(key), (D, F)) * 0.2,
                    "w_down": jax.random.normal(next(key), (F, D)) * 0.2}}
    h = jax.random.normal(next(key), (T, D))
    return cfg, p, h, jnp.ones((T,), bool)


def test_sixteen_ranges_add_up_to_the_uncut_layer():
    """The cut of the benchmark configuration at a small size: 16
    chips, each holding 4 of 64 experts and the shared expert, route
    over all 64 outputs and compute their own part. The 16 parts, the
    shared expert counted ONCE, add up to the uncut REFERENCE's layer
    within float32 rounding; and the reference told one range gives
    that chip's part."""
    cfg, p, h, real = _sparse_layer()
    ref_w = {k: (jax.tree.map(lambda a: a[None], v)) for k, v in dict(
        p, mlp_norm_scale=jnp.ones((h.shape[1],))).items()}
    uncut = _file_config(cfg, experts_held=None, n_routed_experts=64)
    normed = reference._rmsnorm(h, ref_w["mlp_norm_scale"][0], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference._sparse_ffn(uncut, ref_w, 0, h, False, 0)
        whole = np.asarray(whole - h)
        shared = np.asarray(transformer._ffn(cfg, p["shared"], normed))
        parts, held = [], []
        for lo in range(0, 64, 4):
            part_cfg = dataclasses.replace(cfg, experts_held=(lo, lo + 4))
            share = dict(p, **{k: p[k][lo:lo + 4] for k in ("w_gate", "w_up", "w_down")})
            out, n = fam.sparse_ffn(part_cfg, share, normed, real)
            parts.append(np.asarray(out) - shared)       # the routed part alone
            held.append(np.asarray(n))
            if lo == 8:   # the reference's share is the program's
                ref_share = {k: (v[:, lo:lo + 4] if k in ("w_gate", "w_up", "w_down") else v)
                             for k, v in ref_w.items()}
                ref_part, _ = reference._sparse_ffn(
                    _file_config(part_cfg), ref_share, 0, h, False, 0)
                np.testing.assert_allclose(np.asarray(ref_part - h), np.asarray(out),
                                           rtol=0, atol=1e-5 * np.abs(whole).max())
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=0,
                               atol=1e-5 * np.abs(whole).max())
    # every real token's 8 pairs land on exactly one chip
    assert np.concatenate(held).sum() == h.shape[0] * 8


# --- (f) the pool's bytes, the configuration, the refusals --------------------


def test_a_line_is_1152_bytes_a_token_and_layer_from_the_pool_arrays():
    """The published widths in bf16: init_paged_kv_cache's own shapes
    give 512 + 64 values, 1152 B, a token and layer; the engine's
    estimate reads the family's arrays, not heads x head size."""
    cfg = fam.config(num_hidden_layers=2, dtype=jnp.bfloat16)
    pages, page = 6, 128
    cache = jax.eval_shape(lambda: fam.init_paged_kv_cache(cfg, pages, page))
    assert set(cache) == set(fam.PAGE_POOLS) and "k" not in cache
    total = sum(math.prod(a.shape) * a.dtype.itemsize for a in cache.values())
    assert total / (2 * (pages + 1) * page) == 1152
    assert all(a.shape[-1] % 128 == 0 for a in cache.values())    # whole lane tiles
    # Mistral's 8 K/V heads of 128 are 4096 B
    assert 2 * 8 * 128 * 2 == 4096


def test_the_engine_counts_a_line_from_the_family_arrays(shared):
    eng = shared.engine
    cfg = eng.cfg
    per_line = cfg.num_hidden_layers * cfg.line_dim * 4       # float32 pool
    assert eng.kv_bytes_per_line() == per_line
    assert eng.slot_state_bytes() == 0
    assert eng.pager.ensure(0, 20)
    assert eng.kv_allocated_bytes() == 2 * PAGE * per_line
    eng.pager.release(0)


def test_from_hf_reads_the_catalog_row_and_the_benchmark_configuration():
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v3", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                         "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096, "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 129280}
    cfg = fam.from_hf(published, dtype=jnp.bfloat16)
    assert cfg == fam.config(dtype=jnp.bfloat16)       # the family's defaults ARE the row
    assert (cfg.count("dense"), cfg.count("sparse"), cfg.held) == (3, 58, (0, 256))
    assert cfg.head_dim == 192 and cfg.line_dim == 576 and cfg.num_nextn_predict_layers == 1
    # 671 B: 3 dense layers, 58 sparse ones, embedding and head
    assert abs(fam.num_params(cfg) / 1e9 - 671.0) < 1.0
    with open(os.path.join(ROOT, "benchmarks", "configs", "deepseek-v3.json")) as f:
        hf = json.load(f)
    for key, value in published.items():               # every width as published
        if key not in hf["reduced"]:
            assert hf[key] == value, key
    cut = fam.from_hf(hf, dtype=jnp.bfloat16)
    assert cut.kinds == (("mla", "dense"),) + (("mla", "sparse"),) * 4
    assert (cut.n_routed_experts, cut.held, cut.vocab_size) == (256, (0, 16), 16160)
    assert (cut.n_group, cut.topk_group, cut.num_experts_per_tok) == (8, 4, 8)
    # 583.5 M + 4 x 937.6 M + 231.7 M: 4565.6 M parameters, 9.13 GB
    assert abs(fam.num_params(cut) / 1e6 - 4565.6) < 0.5
    shapes = jax.eval_shape(lambda: fam.init_params(jax.random.PRNGKey(0), cut))
    names = {path[-1].key for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    # harness/model.py zeroes a leaf whose name holds "bias" or starts with "b"
    assert not [n for n in names if "bias" in n or n[0] == "b"]
    assert fam.step_counts(cut) == {"moe_counts": (4, 16)}
    # a smaller depth keeps the leading dense layer
    assert fam.from_hf(hf, num_hidden_layers=2).kinds == (("mla", "dense"), ("mla", "sparse"))


@pytest.mark.parametrize("serving, names", [
    (dict(prefix_caching=True), "prefix_caching"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(fused_decode=("rope_kv_write",)), "rope_kv_write"),
    (dict(fused_decode=("sampling",)), "unknown fused_decode entry 'sampling'"),
    (dict(kv_shard="context", context_shards=2), "kv_shard"),
    (dict(kv_layout="dense"), "kv_layout"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_refused_combinations_name_their_reason(tiny, tiny_servers, serving, names):
    cfg, params = tiny
    with pytest.raises((NotImplementedError, ValueError), match=names):
        InferenceEngine(fam, cfg, params, tiny_servers.serving(**serving))


def test_a_model_parallel_mesh_is_refused(tiny, tiny_servers):
    from flexflow_tpu.core.mesh import MachineSpec

    cfg, params = tiny
    mesh = MachineSpec(model=2).make_mesh(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="model > 1"):
        InferenceEngine(fam, cfg, params, tiny_servers.serving(), mesh)


@pytest.mark.parametrize("draft", ["ssm", "early_exit"])
def test_speculation_is_refused(tiny, tiny_servers, draft):
    from flexflow_tpu.serve import SpecConfig
    from flexflow_tpu.serve.llm import SSM

    cfg, params = tiny
    llm = LLM(fam, cfg, params=params)
    ssms = [SSM(fam, cfg, params=params)] if draft == "ssm" else []
    spec = SpecConfig(draft=draft, draft_layers=1) if draft == "early_exit" else None
    with pytest.raises(NotImplementedError, match="SpecInfer"):
        llm.compile(tiny_servers.serving(), ssms=ssms, spec=spec)


def test_beam_search_is_refused(shared):
    from flexflow_tpu.serve import GenerationConfig

    with pytest.raises(NotImplementedError, match="latent page pool"):
        shared.generate([[1, 2, 3]], GenerationConfig(num_beams=2, max_new_tokens=2))


def test_the_reference_bounds_its_routings():
    """At most 2^4 routings a judged token: routing 0 is float32's own
    and is the full forward pass; a routing flips only choices (two a
    sparse layer: the last group, the last expert) under the file's
    routing_margin; the int8 control is one routing and reads orders
    over float32's rounding."""
    margins = np.asarray([[0.30, 0.01, 0.04, 0.02, 0.5, 0.03, 0.011],
                          [0.30, 0.20, 0.04, 0.40, 0.5, 0.60, 0.700]], np.float32)
    flips, valid = reference.flipped_choices(margins, 0.05)
    assert flips.shape == (2, 16, 7) and not flips[:, 0].any()
    assert flips[0, 15].tolist() == [False, True, False, True, False, True, True]
    assert valid[0].all() and valid[1].tolist() == [True, True] + [False] * 14
    cfg = fam.tiny(dtype=jnp.float32)
    params = fam.init_params(jax.random.PRNGKey(1), cfg)
    tokens = np.random.default_rng(10).integers(0, cfg.vocab_size, (2, 24))
    judge = np.asarray([[23, 11], [5, 0]])
    file_cfg = _file_config(cfg, tolerance={"routing_margin": 0.5})
    logits, flip_margin, margin = reference.judged_logits(params, file_cfg, tokens, judge)
    assert logits.shape == (2, 2, 16, cfg.vocab_size) and flip_margin.shape == (2, 2, 16)
    full = reference.forward(params, file_cfg, tokens)
    for b in range(2):
        for j in range(2):
            np.testing.assert_allclose(logits[b, j, 0], full[b, judge[b, j]], atol=1e-5)
    assert (flip_margin[:, :, 0] == 0).all() and (margin >= 0).all()
    taken = np.isfinite(flip_margin) & (flip_margin > 0)
    assert taken.any()      # some routing went the other way, and its logits moved
    assert max(_rms_share(logits[b, j, r], logits[b, j, 0])
               for b, j, r in zip(*np.nonzero(taken))) > 1e-4
    one, _, _ = reference.judged_logits(params, file_cfg, tokens, judge, control_bits=8)
    assert one.shape == (2, 2, 1, cfg.vocab_size)
    assert _rms_share(one[0, 0, 0], full[0, 23]) > 1e-3


def _whole_tiles(shape, dtype):
    """``kernels._vmem_bytes`` as it stood before PR 55: the last two
    dims padded to the dtype's whole (sublane, 128-lane) tile."""
    item = jnp.dtype(dtype).itemsize
    *lead, sub, lane = shape
    tile = 8 * max(1, 4 // item)
    return (int(np.prod(lead, dtype=np.int64)) * -(-sub // tile) * tile
            * -(-lane // 128) * 128 * item)


@pytest.mark.parametrize("C, NP", [(128, 81), (1, 81)])
def test_the_latent_kernels_vmem_statement_is_the_one_before_pr_55(C, NP):
    """``mla_paged_attention`` states its VMEM through
    ``kernels._vmem_bytes``, which PR 55 changed for the ragged paged
    kernel: a second-to-last dim UNDER its dtype's tile now pads to the
    power of two that holds it (Mosaic tiles ``bf16[.., 1, 128]`` as (2,
    128)), not to the whole tile. Every buffer the latent call states
    at the DeepSeek cell's shapes (128 heads, latent 512, rope 64, pages
    of 128 lines, the mixed step's 32 columns x 2 pages and the decode
    step's 1 x 8) has its second-to-last dim at or over the tile, so
    its statement is the parent's to the byte."""
    H, V, dr, ps = 128, 512, 64, 128
    TC, KB = kernels.mla_block(C, NP, H, ps)
    assert (TC, KB) == ((32, 2) if C == 128 else (1, 8))
    M, W = TC * H, KB * ps
    bf16, f32 = jnp.bfloat16, jnp.float32
    buffers = ([((1, TC, H, V), bf16), ((1, TC, H, dr), bf16)]   # the queries
               + [((1, ps, V), bf16), ((1, ps // 2, 2 * dr), bf16)] * KB
               + [((1, TC, H, V), bf16)]                          # the result
               + [((M, V), f32), ((M, kernels.STATE_LANES), f32)]  # scratch
               + [((W, V + dr), bf16), ((M, W), f32), ((M, V), f32)])
    for shape, dtype in buffers:
        assert kernels._vmem_bytes(shape, dtype) == _whole_tiles(shape, dtype), shape
    # and what changed: a group of one query head, twice over and not
    # sixteen times
    assert kernels._vmem_bytes((1, 128), bf16) == 2 * 128 * 2
    assert _whole_tiles((1, 128), bf16) == 16 * 128 * 2

"""The llama family against the generic decoder — the twin, held.

``models/llama.py`` repeats ``models/transformer.py`` function for
function (ROADMAP C1). Until the fold, a change made to one of them
(PR 28's in-place pool carry, PR 30's ``q_len``) has to show up in the
other or be shown not to matter: every case here runs the SAME weights
(llama's, through the rename below) through both files at llama's
configuration and compares what comes out — logits, the pool lines a
step writes, the pool after a commit / reorder / page copy, and greedy
generations through ``LLM.generate``. The fold itself is made under
this file: when ``llama`` re-exports the generic decoder these cases
compare a thing with itself and the file goes.

Tolerance: bitwise, as ``tests/test_fused_decode.py``'s step-parity
tests (their 1-ulp allowance on written K lines is not needed here:
both sides compile the same fusion). The one case that is not bitwise
is the training ``forward``, and says why.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import llama, transformer
from flexflow_tpu.serve import ServingConfig
from flexflow_tpu.serve.llm import LLM

VOCAB = 256                       # LLaMAConfig.tiny's
PS, NP, PAGES = 8, 4, 6           # page size, pages a slot, pool pages
CACHE_LEN = NP * PS - 1
TABLE = [[0, 1, PAGES, PAGES], [2, 3, PAGES, PAGES]]  # PAGES = scratch


def as_decoder(cfg, params):
    """llama's config and weights, spelled as the generic decoder's."""
    dcfg = transformer.DecoderConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        max_position_embeddings=cfg.max_position_embeddings,
        norm_type="rmsnorm", norm_bias=False, norm_eps=cfg.rms_norm_eps,
        positions="rope", rope_theta=cfg.rope_theta, activation="silu",
        glu=True, tie_word_embeddings=cfg.tie_word_embeddings,
        dtype=cfg.dtype,
    )
    names = {"attn_norm": "attn_norm_scale", "ffn_norm": "mlp_norm_scale",
             "w1": "w_gate", "w3": "w_up", "w2": "w_down"}
    out = {k: v for k, v in params.items() if k not in ("layers", "final_norm")}
    out["final_norm_scale"] = params["final_norm"]
    out["layers"] = {names.get(k, k): v for k, v in params["layers"].items()}
    return dcfg, out


@pytest.fixture(scope="module")
def twins():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return (llama, cfg, params), (transformer, *as_decoder(cfg, params))


def same(a, b, what):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=what)


def same_pool(a, b):
    """Every buffer of two paged caches, scratch page aside."""
    assert set(a) == set(b)
    for name in a:
        same(a[name][:, :PAGES], b[name][:, :PAGES], f"cache[{name}]")


def step_inputs(C):
    """Two rows: a prompt's chunk of C tokens at positions 3.. and 6..
    (C == 1: a decode step)."""
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, VOCAB, (2, C)), jnp.int32)
    positions = jnp.asarray([[3 + c for c in range(C)],
                             [6 + c for c in range(C)]], jnp.int32)
    return tokens, positions, jnp.full((2,), C - 1, jnp.int32)


def test_forward(twins):
    (la, lcfg, lp), (tr, tcfg, tp) = twins
    tokens = step_inputs(12)[0]
    # NOT bitwise, the one case: llama's training ``attention`` repeats
    # the KV heads and contracts per head, the generic ``_gqa_attend``
    # contracts per KV group; the same sums in another order (1.5e-7 on
    # logits of order 0.3 here). ROADMAP C1 names it for the fold.
    np.testing.assert_allclose(
        np.asarray(la.forward(lp, tokens, lcfg)),
        np.asarray(tr.forward(tp, tokens, tcfg)), rtol=0, atol=1e-6,
    )


@pytest.mark.parametrize("C", [1, 8])
def test_dense_serve_step(twins, C):
    outs = []
    tokens, positions, lidx = step_inputs(C)
    for model, cfg, params in twins:
        cache = model.init_kv_cache(cfg, 2, CACHE_LEN)
        step = jax.jit(functools.partial(model.serve_step, cfg=cfg))
        outs.append(step(params, cache, tokens, positions, lidx, None))
    (ll, lc), (tl, tc) = outs
    same(ll, tl, "logits")
    for name in ("k", "v"):
        same(lc[name], tc[name], f"cache[{name}]")


def paged_step(twins, C, kernels, kv_quant, fused_rope=False):
    tokens, positions, lidx = step_inputs(C)
    pt = jnp.asarray(TABLE, jnp.int32)
    outs = []
    for model, cfg, params in twins:
        cache = model.init_paged_kv_cache(cfg, PAGES, PS, kv_quant=kv_quant)
        step = jax.jit(functools.partial(
            model.serve_step_paged, cfg=cfg, cache_len=CACHE_LEN,
            kernels=kernels, kv_quant=kv_quant, fused_rope=fused_rope,
        ))
        outs.append(step(params, cache, tokens, positions, lidx, None, None,
                         pt))
    return outs


@pytest.mark.parametrize("C", [1, 8])
@pytest.mark.parametrize("kv_quant", [None, "int8", "int4"])
@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_paged_serve_step(twins, kernels, kv_quant, C):
    """Logits of the real rows and the pool lines the step wrote."""
    (ll, lc), (tl, tc) = paged_step(twins, C, kernels, kv_quant)
    same(ll, tl, "logits")
    same_pool(lc, tc)


@pytest.mark.parametrize("C", [1, 8])
def test_paged_serve_step_fused_rope(twins, C):
    (ll, lc), (tl, tc) = paged_step(twins, C, "pallas", None, fused_rope=True)
    same(ll, tl, "logits")
    same_pool(lc, tc)


def _written_pools(twins):
    return [c for _, c in paged_step(twins, 8, "xla", None)]


def _commit(model, cache, pt):
    src = jnp.asarray([[5, 7], [9, 10]], jnp.int32)
    dst = jnp.asarray([[3, 4], [6, 7]], jnp.int32)
    return model.commit_kv_paged(cache, pt, src, dst)


def _reorder(model, cache, pt):
    return model.reorder_slots_paged(cache, pt, jnp.asarray([1, 1], jnp.int32))


def _copy_page(model, cache, pt):
    return model.copy_page_kv(cache, jnp.int32(2), jnp.int32(4))


@pytest.mark.parametrize("op", [_commit, _reorder, _copy_page],
                         ids=["commit_kv_paged", "reorder_slots_paged",
                              "copy_page_kv"])
def test_pool_ops(twins, op):
    pt = jnp.asarray(TABLE, jnp.int32)
    before = _written_pools(twins)
    lc, tc = (jax.jit(functools.partial(op, model))(cache, pt)
              for (model, _, _), cache in zip(twins, before))
    same_pool(lc, tc)
    # and the operation did something: it is not the pool it was given
    assert any(np.any(np.asarray(lc[n]) != np.asarray(before[0][n]))
               for n in lc)


PROMPTS = [[(i * 7 + j * 3 + 1) % 256 for j in range(9 + i)] for i in range(3)]
PROMPTS.append(PROMPTS[0] + [5, 6])  # shares a page of prefix with the first


@pytest.mark.parametrize("prefix_caching", [False, True])
def test_greedy_generation(twins, prefix_caching):
    outs = []
    for model, cfg, params in twins:
        llm = LLM(model, cfg, params=params)
        llm.compile(ServingConfig(
            max_requests_per_batch=2, max_sequence_length=48,
            prefill_chunk=8, cache_dtype=jnp.float32, kv_layout="paged",
            page_size=8, kernels="pallas", prefix_caching=prefix_caching,
        ))
        outs.append([r.output_tokens
                     for r in llm.generate(PROMPTS, max_new_tokens=6)])
        if prefix_caching:
            assert llm.rm.stats.prefix_hits > 0
    assert outs[0] == outs[1]
    assert all(len(o) == 6 for o in outs[0])

"""Flash-attention kernel numerics: fwd + custom-VJP bwd vs the XLA
attention path (ADVICE r3 medium: the 363-line Pallas kernel had no
direct test coverage). Runs interpret=True on the CPU mesh."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.core.mesh import set_mesh as _set_mesh
from flexflow_tpu.models import llama, transformer
from flexflow_tpu.ops.flash_attention import flash_attention


def _ref_attention(q, k, v, causal):
    """Plain XLA attention over (B, S, H, dk) with pre-repeated heads."""
    S, T = q.shape[1], k.shape[1]
    scores = jnp.einsum(
        "bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32
    ) / np.sqrt(q.shape[-1])
    if causal:
        mask = jnp.tril(jnp.ones((S, T), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _qkv(B, S, H, dk, key=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    mk = lambda k: jax.random.normal(k, (B, S, H, dk), dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


# Non-block-aligned S (block_q/block_k = 16 vs S = 24/40) exercises the
# padded-block masking and the NaN guards on out-of-bounds rows.
@pytest.mark.parametrize("S", [16, 24, 40])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_xla(S, causal):
    q, k, v = _qkv(2, S, 2, 32)
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    want = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("S", [16, 24])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_xla(S, causal):
    q, k, v = _qkv(1, S, 2, 16, key=1)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        return (out.astype(jnp.float32) ** 2).mean()

    def loss_ref(q, k, v):
        out = _ref_attention(q, k, v, causal)
        return (out.astype(jnp.float32) ** 2).mean()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name} mismatch (S={S}, causal={causal})",
        )


def test_flash_gqa_via_model_attn_fn():
    """make_flash_attention repeats the compact KV heads before the
    kernel — must equal the decoder's grouped XLA attention."""
    cfg = llama.LLaMAConfig(
        vocab_size=64, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=32, dtype=jnp.float32,
    )
    B, S = 2, 24
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, 2, 16), jnp.float32)
    attn_fn = llama.make_flash_attention(block_q=16, block_k=16)
    got = attn_fn(cfg, q, k, v, None)
    want = transformer._gqa_attend(
        cfg, q, k, v, None, jnp.tril(jnp.ones((S, S), bool))
    ).reshape(got.shape)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_make_train_step_flash_smoke():
    """attention='flash' end-to-end: one optimizer step compiles, runs,
    and produces a finite loss matching the XLA path closely."""
    from flexflow_tpu.core.mesh import MachineSpec
    from flexflow_tpu.optimizers import SGDOptimizer

    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    mesh = MachineSpec().make_mesh(jax.devices()[:1])
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 32)
    ).astype(np.int32)
    losses = {}
    with _set_mesh(mesh):
        for attn in ("xla", "flash"):
            init_fn, step, ds = llama.make_train_step(
                cfg, mesh, SGDOptimizer(lr=0.0), remat=True,
                shard_activations=False, attention=attn,
            )
            params, opt = init_fn(jax.random.PRNGKey(0))
            _, _, loss = step(params, opt, jax.device_put(tokens, ds))
            losses[attn] = float(loss)
    assert np.isfinite(losses["flash"])
    assert losses["flash"] == pytest.approx(losses["xla"], rel=1e-4)


def test_remat_policy_dots_same_numerics():
    """remat_policy='dots' changes what backward recomputes, not the
    math: loss must match full remat bitwise-ish."""
    from flexflow_tpu.core.mesh import MachineSpec
    from flexflow_tpu.optimizers import SGDOptimizer

    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    mesh = MachineSpec().make_mesh(jax.devices()[:1])
    tokens = np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=(2, 24)
    ).astype(np.int32)
    losses = {}
    with _set_mesh(mesh):
        for pol in (None, "dots"):
            init_fn, step, ds = llama.make_train_step(
                cfg, mesh, SGDOptimizer(lr=0.1), remat=True,
                remat_policy=pol, shard_activations=False,
            )
            params, opt = init_fn(jax.random.PRNGKey(0))
            # two steps so the optimizer update (i.e. the grads) matters
            params, opt, _ = step(params, opt, jax.device_put(tokens, ds))
            _, _, loss = step(params, opt, jax.device_put(tokens, ds))
            losses[pol] = float(loss)
    assert losses["dots"] == pytest.approx(losses[None], rel=1e-5)

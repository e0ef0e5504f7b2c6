"""The train step on the generic decoder (ISSUE 49, the head of ROADMAP
B5): ``make_train_step`` takes any ``DecoderConfig``, ``forward`` takes
an attention override and a remat policy. The llama configuration's own
cases are tests/test_llama.py, test_sequence_parallel.py and
test_flash_attention.py; here are the families it never reached.

Tiny widths, float32, one CPU device unless a case says otherwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.core.mesh import MachineSpec, set_mesh
from flexflow_tpu.models import llama, mistral, opt, qwen2, transformer
from flexflow_tpu.optimizers import AdamOptimizer, SGDOptimizer

KEY = jax.random.PRNGKey(0)

# what llama's configuration does not have: a window; QKV biases;
# LayerNorm with biases, learned positions and a tied head
CONFIGS = {
    "mistral_window": lambda **kw: mistral.tiny(dtype=jnp.float32, **kw),
    "qwen2_qkv_bias": lambda **kw: qwen2.tiny(dtype=jnp.float32, **kw),
    "opt_layernorm_learned": lambda **kw: opt.tiny(dtype=jnp.float32, **kw),
}


def _tokens(cfg, shape=(4, 16)):
    return jax.random.randint(KEY, shape, 0, cfg.vocab_size, dtype=jnp.int32)


def _losses(cfg, mesh, optimizer, tokens, steps, **kw):
    with set_mesh(mesh):
        init_fn, step, ds = transformer.make_train_step(
            cfg, mesh, optimizer, **kw)
        params, opt_state = init_fn(KEY)
        toks = jax.device_put(tokens, ds)
        out = []
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, toks)
            out.append(float(loss))
    return out


@pytest.mark.parametrize("name", list(CONFIGS))
def test_train_step_lowers_the_loss(name):
    cfg = CONFIGS[name]()
    assert {"mistral_window": cfg.sliding_window > 0,
            "qwen2_qkv_bias": cfg.qkv_bias,
            "opt_layernorm_learned": cfg.positions == "learned"
            and cfg.norm_type == "layernorm"}[name]
    mesh = MachineSpec().make_mesh(jax.devices()[:1])
    losses = _losses(cfg, mesh, AdamOptimizer(lr=1e-2), _tokens(cfg), 5,
                     remat=False, shard_activations=False)
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.1, losses


def test_pipelined_loss_matches_one_device():
    """pipe=2 x data=4 against one device, three SGD steps: the pipelined
    loss embeds (learned positions), norms (LayerNorm with a bias) and
    projects (a tied head) through the decoder's own functions."""
    cfg = CONFIGS["opt_layernorm_learned"](num_hidden_layers=4)
    tokens = np.asarray(_tokens(cfg, (8, 16)))
    one = _losses(cfg, MachineSpec().make_mesh(jax.devices()[:1]),
                  SGDOptimizer(lr=0.1), tokens, 3)
    piped = _losses(cfg, MachineSpec.from_degrees(8, pipeline=2).make_mesh(),
                    SGDOptimizer(lr=0.1), tokens, 3, num_microbatches=2)
    np.testing.assert_allclose(piped, one, rtol=2e-6)


@pytest.mark.parametrize("name", ["llama_gqa", "qwen2_qkv_bias"])
def test_forward_flash_override_matches_default(name):
    """``attn_fn=make_flash_attention()`` (the Pallas kernel, interpret
    mode here; K/V handed over compact) against the grouped XLA
    attention: float32 logits of order 0.3 to a few ulp of their
    different summation orders."""
    cfg = (llama.LLaMAConfig.tiny(dtype=jnp.float32) if name == "llama_gqa"
           else CONFIGS[name]())
    assert cfg.num_key_value_heads < cfg.num_attention_heads
    params = transformer.init_params(KEY, cfg)
    tokens = _tokens(cfg, (2, 32))
    want = transformer.forward(params, tokens, cfg)
    got = transformer.forward(
        params, tokens, cfg,
        attn_fn=transformer.make_flash_attention(block_q=16, block_k=16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6)


def test_attention_override_refuses_a_window():
    """An override is plain causal attention: a family whose mask is
    more than causal is refused, not served the wrong mask."""
    cfg = CONFIGS["mistral_window"]()
    params = jax.eval_shape(lambda: transformer.init_params(KEY, cfg))
    with pytest.raises(ValueError, match="no sliding window"):
        jax.eval_shape(lambda p: transformer.forward(
            p, jnp.zeros((1, 8), jnp.int32), cfg,
            attn_fn=transformer.make_flash_attention()), params)


def test_remat_policy_dots_changes_memory_not_math():
    """``remat_policy="dots"`` against full remat and none: the same
    loss and the same gradients."""
    cfg = CONFIGS["qwen2_qkv_bias"]()
    params = transformer.init_params(KEY, cfg)
    tokens = _tokens(cfg)

    def grads(**kw):
        return jax.jit(jax.value_and_grad(
            lambda p: transformer.next_token_loss(p, tokens, cfg, **kw)
        ))(params)

    (l0, g0), (l1, g1), (l2, g2) = (
        grads(), grads(remat=True), grads(remat=True, remat_policy="dots"))
    assert float(l0) == float(l1) == float(l2)
    for a, b, c in zip(*(jax.tree.leaves(g) for g in (g0, g1, g2))):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=0, atol=1e-7)
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   rtol=0, atol=1e-7)
    with pytest.raises(ValueError, match="unknown remat policy"):
        grads(remat=True, remat_policy="dot")

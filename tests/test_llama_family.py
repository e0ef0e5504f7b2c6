"""The llama family on the generic decoder (ISSUE 49): the configuration
is a ``DecoderConfig`` with llama's defaults, and the family's served
step is the decoder's — packed rungs, ``q_len``, the dense layout's one
attention — with nothing of its own.

Tiny widths on the CPU; the Pallas kernels in interpret mode.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import llama, mistral, transformer
from flexflow_tpu.serve import InferenceEngine
from flexflow_tpu.serve.engine import program_name

# ---------------------------------------------------------------------------
# the configuration survives the fold

# preset -> (head_dim, num_params, flops_per_token at 128 positions), as
# models/llama.py's own formulas gave them before it became a config
PRESETS = {
    "tiny": (16, 106816, 279168),
    "llama_160m": (64, 162417408, 329553408),
    "llama_7b": (128, 6738415616, 13543940096),
}


@pytest.mark.parametrize("preset", list(PRESETS))
def test_preset_sizes(preset):
    cfg = getattr(llama.LLaMAConfig, preset)()
    assert (cfg.head_dim, llama.num_params(cfg),
            llama.flops_per_token(cfg, 128)) == PRESETS[preset]


def test_config_is_a_decoder_config_with_llamas_defaults():
    cfg = llama.LLaMAConfig()
    assert isinstance(cfg, transformer.DecoderConfig)
    assert (cfg.norm_type, cfg.norm_bias, cfg.norm_eps) == ("rmsnorm", False, 1e-6)
    assert (cfg.glu, cfg.activation, cfg.positions) == (True, "silu", "rope")
    assert not cfg.tie_word_embeddings and not cfg.sliding_window
    # the parameter names are the decoder's, and there are no others
    shapes = transformer.init_shapes(llama.LLaMAConfig.tiny())
    assert set(shapes) == {"embed", "layers", "final_norm_scale", "lm_head"}
    assert set(shapes["layers"]) == {
        "attn_norm_scale", "mlp_norm_scale", "wq", "wk", "wv", "wo",
        "w_gate", "w_up", "w_down"}


def test_from_hf_llama2_style():
    hf = {  # meta-llama/Llama-2-7b-hf config.json, the fields read
        "vocab_size": 32000, "hidden_size": 4096, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 32,
        "num_key_value_heads": 32, "rms_norm_eps": 1e-05,
        "max_position_embeddings": 4096, "tie_word_embeddings": False,
        "hidden_act": "silu", "model_type": "llama",
    }
    cfg = llama.from_hf(hf, dtype=jnp.float32)
    assert type(cfg) is llama.LLaMAConfig
    assert cfg == dataclasses.replace(
        llama.LLaMAConfig.llama_7b(dtype=jnp.float32), norm_eps=1e-05,
        max_position_embeddings=4096)
    # an older config without GQA's field: the heads are the KV heads
    del hf["num_key_value_heads"]
    assert llama.from_hf(hf).num_key_value_heads == 32


def test_replace_keeps_the_type():
    cfg = dataclasses.replace(llama.LLaMAConfig.tiny(), num_hidden_layers=3)
    assert type(cfg) is llama.LLaMAConfig and cfg.num_hidden_layers == 3
    assert cfg.head_dim == 16 and hash(cfg) == hash(
        llama.LLaMAConfig.tiny(num_hidden_layers=3))


# ---------------------------------------------------------------------------
# the family's served step is the decoder's

R, C, PS = 6, 8, 8           # ladder (12, 24, 48): two packed rungs


@pytest.fixture(scope="module")
def tiny(tiny_servers):
    return tiny_servers.params(llama)


@pytest.fixture
def serving(tiny_servers):
    """The geometry is the test (the compile counts below name its rungs);
    each case owns its engine: it counts compiles or spies on a trace."""
    return lambda **kw: tiny_servers.serving(
        max_requests_per_batch=R, max_sequence_length=56, prefill_chunk=C,
        max_spec_tree_tokens=8, page_size=PS, **kw)


def _mixed_step(eng, feed):
    """One mixed step: ``feed`` row -> new prompt tokens from position 0."""
    toks = np.zeros((R, C), np.int32)
    pos = np.full((R, C), eng.scratch_pos, np.int32)
    idx = np.zeros((R,), np.int32)
    for row, n in feed.items():
        toks[row, :n] = np.arange(3, 3 + n)
        pos[row, :n] = np.arange(n)
        idx[row] = n - 1
        assert eng.pager.ensure(row, n)
    ones = np.ones(R, np.float32)
    return eng.run_mixed(
        np.zeros(R, np.int32), toks, np.zeros(R, bool), pos, idx,
        jax.random.PRNGKey(0), np.ones(R, bool), ones, ones,
        np.zeros(R, np.int32))


def test_mixed_step_runs_at_a_packed_rung(tiny, serving):
    """PR 32, PR 45: the family's engine has the ladder and a step of
    few real tokens runs the rung's program, not the padded one."""
    eng = InferenceEngine(llama, *tiny, serving(
        kv_layout="paged", kernels="pallas", sanitizers=("retrace",)))
    assert eng.pack_ladder(C) == (12, 24) and eng.pack_ladder(1) == ()
    ran = []
    hook = eng._poison_donated
    eng._poison_donated = lambda donated, key: (ran.append(key),
                                                hook(donated, key))
    np.asarray(_mixed_step(eng, {0: 8, 1: 3}))
    assert ran == [("mixed_packed", C, 12, "greedy", 0)]
    assert program_name(ran[0]) == "ff_step_c8_t12"
    # the whole ladder and the padded program, once each
    assert eng.retrace_guard.compile_counts() == {
        ("mixed_packed", C, 12, "greedy", 0): 1,
        ("mixed_packed", C, 24, "greedy", 0): 1,
        ("mixed_fused", C, False, "greedy", 0): 1,
    }


def test_kernel_call_is_told_the_real_queries(tiny, monkeypatch, serving):
    """PR 30: the ragged paged kernel of the family's step receives
    ``q_len``, the real queries of each row."""
    from flexflow_tpu.serve import kernels

    seen = []
    real = kernels.ragged_paged_attention

    def spy(*args, q_len=None, **kw):
        seen.append(q_len)
        return real(*args, q_len=q_len, **kw)

    monkeypatch.setattr(kernels, "ragged_paged_attention", spy)
    eng = InferenceEngine(llama, *tiny, serving(
        kv_layout="paged", kernels="pallas"))
    eng.pack_ladder = lambda chunk: ()  # the padded program: one trace
    np.asarray(_mixed_step(eng, {0: 8, 2: 1}))
    assert seen and all(q is not None and q.shape == (R,) for q in seen)


@pytest.mark.parametrize("family", [llama, mistral], ids=["llama", "mistral"])
def test_dense_layout_refuses_pallas(family, serving):
    """The dense layout is the XLA reference layout: asking it for the
    Pallas kernels fails at construction (before the weights are looked
    at), in a sentence, for every family alike."""
    with pytest.raises(ValueError, match="requires kv_layout='paged'.*dense "
                       "layout is the XLA reference layout"):
        InferenceEngine(family, family.tiny(dtype=jnp.float32), None,
                        serving(kv_layout="dense", kernels="pallas"))

"""LLaMA model family (HF ``LlamaForCausalLM``; reference
``inference/models/llama.cc:23-280`` and ``python/flexflow/serve/models/
llama.py``): embedding → N × [rms_norm → attention(QKV+RoPE+GQA) →
residual_rms_norm → SwiGLU FFN] → rms_norm → lm_head. Runs on the
generic decoder (:mod:`.transformer`): this file is the configuration,
its presets and the HF converter."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax.numpy as jnp

from .transformer import (  # noqa: F401  (engine serving protocol + training)
    DecoderConfig,
    FUSED_DECODE,
    PACKED_STEP,
    commit_kv,
    commit_kv_paged,
    copy_page_kv,
    flops_per_token,
    forward,
    gather_page_kv,
    init_kv_cache,
    init_paged_kv_cache,
    init_params,
    kv_cache_pspecs,
    make_flash_attention,
    make_sp_attention,
    make_train_step,
    next_token_loss,
    num_params,
    paged_kv_cache_pspecs,
    param_pspecs,
    reorder_slots,
    reorder_slots_paged,
    scatter_page_kv,
    serve_debug_activations,
    serve_step,
    serve_step_paged,
)
from .hf_utils import layer_stackers, linear_w, to_np


@dataclasses.dataclass(frozen=True)
class LLaMAConfig(DecoderConfig):
    """A :class:`DecoderConfig` whose defaults are LLaMA-7B's."""

    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    norm_type: str = "rmsnorm"
    norm_bias: bool = False
    norm_eps: float = 1e-6
    activation: str = "silu"
    glu: bool = True
    tie_word_embeddings: bool = False

    @classmethod
    def llama_7b(cls, **kw):
        return cls(**kw)

    @classmethod
    def llama_160m(cls, **kw):
        """The reference's standard SSM speculator (JackFram/llama-160m)."""
        d = dict(
            hidden_size=768,
            intermediate_size=3072,
            num_hidden_layers=12,
            num_attention_heads=12,
            num_key_value_heads=12,
        )
        d.update(kw)
        return cls(**d)

    @classmethod
    def tiny(cls, **kw):
        d = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            max_position_embeddings=128,
        )
        d.update(kw)
        return cls(**d)

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **kw) -> "LLaMAConfig":
        d = dict(
            vocab_size=hf.get("vocab_size", 32000),
            hidden_size=hf.get("hidden_size", 4096),
            intermediate_size=hf.get("intermediate_size", 11008),
            num_hidden_layers=hf.get("num_hidden_layers", 32),
            num_attention_heads=hf.get("num_attention_heads", 32),
            num_key_value_heads=hf.get(
                "num_key_value_heads", hf.get("num_attention_heads", 32)
            ),
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            rope_theta=hf.get("rope_theta", 10000.0),
            max_position_embeddings=hf.get("max_position_embeddings", 2048),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
        )
        d.update(kw)
        return cls(**d)


# the entry points every family module has (``config``, ``tiny``,
# ``from_hf``), so callers need no branch on the family
config = LLaMAConfig
tiny = LLaMAConfig.tiny
from_hf = LLaMAConfig.from_hf


def convert_hf_state_dict(
    sd: Dict[str, Any], cfg: DecoderConfig
) -> Dict[str, Any]:
    """HF ``LlamaForCausalLM`` state dict → framework pytree (stacked
    layer dim); ``MistralForCausalLM`` has the same tensor names. The
    analog of the reference's per-layer weight-file conversion
    (reference ``python/flexflow/serve/serve.py:167-227``,
    ``inference/file_loader.cc:792``)."""
    dt = cfg.dtype
    pre = "model."
    mats, vecs = layer_stackers(sd, pre, cfg.num_hidden_layers, dt)
    layers = {
        "attn_norm_scale": vecs("layers.{}.input_layernorm.weight"),
        "mlp_norm_scale": vecs("layers.{}.post_attention_layernorm.weight"),
        "wq": mats("layers.{}.self_attn.q_proj.weight"),
        "wk": mats("layers.{}.self_attn.k_proj.weight"),
        "wv": mats("layers.{}.self_attn.v_proj.weight"),
        "wo": mats("layers.{}.self_attn.o_proj.weight"),
        "w_gate": mats("layers.{}.mlp.gate_proj.weight"),
        "w_up": mats("layers.{}.mlp.up_proj.weight"),
        "w_down": mats("layers.{}.mlp.down_proj.weight"),
    }
    params: Dict[str, Any] = {
        "embed": jnp.asarray(to_np(sd[pre + "embed_tokens.weight"]), dt),
        "layers": layers,
        "final_norm_scale": jnp.asarray(to_np(sd[pre + "norm.weight"]), dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(linear_w(sd, "lm_head.weight"), dt)
    return params

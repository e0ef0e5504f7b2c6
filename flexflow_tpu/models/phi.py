"""Phi model family (HF ``PhiForCausalLM``, Phi-1/1.5/2) — beyond the
reference zoo. Runs on the generic decoder with partial rotary
embeddings (``rotary_pct``: only the first fraction of each head
rotates), a Falcon-style parallel block sharing one input LayerNorm,
biased everything (QKV/out/MLP/LM head), and gelu_tanh FFN."""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from . import transformer
from .transformer import (  # noqa: F401  (engine serving protocol)
    DecoderConfig,
    FUSED_DECODE,
    PACKED_STEP,
    commit_kv,
    commit_kv_paged,
    copy_page_kv,
    forward,
    gather_page_kv,
    init_kv_cache,
    init_paged_kv_cache,
    init_params,
    kv_cache_pspecs,
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
from .hf_utils import layer_stackers, to_np


def config(**kw) -> DecoderConfig:
    d: Dict[str, Any] = dict(
        vocab_size=51200,
        hidden_size=2560,
        intermediate_size=10240,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=32,
        max_position_embeddings=2048,
        norm_type="layernorm",
        norm_bias=True,
        norm_eps=1e-5,
        positions="rope",
        rope_theta=10000.0,
        rotary_pct=0.4,
        activation="gelu_tanh",
        glu=False,
        parallel_block=True,
        parallel_two_norms=False,
        qkv_bias=True,
        out_bias=True,
        mlp_bias=True,
        tie_word_embeddings=False,
        lm_head_bias=True,
    )
    d.update(kw)
    return DecoderConfig(**d)


def phi_2(**kw) -> DecoderConfig:
    return config(**kw)


def tiny(**kw) -> DecoderConfig:
    d = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        max_position_embeddings=128,
        rotary_pct=0.5,
    )
    d.update(kw)
    return config(**d)


_HF_ACTS = {
    "gelu_new": "gelu_tanh",
    "gelu_pytorch_tanh": "gelu_tanh",
    "gelu_fast": "gelu_tanh",
    "gelu": "gelu",
    "relu": "relu",
    "silu": "silu",
}


def from_hf(hf: Dict[str, Any], **kw) -> DecoderConfig:
    mt = hf.get("model_type", "phi")
    if mt != "phi":
        # detect_family's substring fallback would route phi3/phi4/
        # phimoe checkpoints here; their fused qkv/gate_up projections
        # and SwiGLU do not fit this converter
        raise NotImplementedError(
            f"model_type {mt!r} is not Phi-1/2; phi3/phi4/phimoe "
            "architectures are unsupported"
        )
    if hf.get("qk_layernorm"):
        # q/k per-head layernorm weights would be silently dropped —
        # wrong logits with no error
        raise NotImplementedError(
            "Phi qk_layernorm=True is not supported"
        )
    act = hf.get("hidden_act", "gelu_new")
    d = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf.get(
            "num_key_value_heads", hf["num_attention_heads"]
        ),
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=hf.get("layer_norm_eps", 1e-5),
        rope_theta=hf.get("rope_theta", 10000.0),
        rotary_pct=hf.get("partial_rotary_factor", 0.5),
        activation=_HF_ACTS.get(act, act),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
    )
    d.update(kw)
    return config(**d)


def convert_hf_state_dict(
    sd: Dict[str, Any], cfg: DecoderConfig
) -> Dict[str, Any]:
    """HF ``PhiForCausalLM`` state dict → framework pytree."""
    dt = cfg.dtype
    L = cfg.num_hidden_layers
    pre = "model."
    mats, vecs = layer_stackers(sd, pre, L, dt)

    layers = {
        "attn_norm_scale": vecs("layers.{}.input_layernorm.weight"),
        "attn_norm_bias": vecs("layers.{}.input_layernorm.bias"),
        "wq": mats("layers.{}.self_attn.q_proj.weight"),
        "wk": mats("layers.{}.self_attn.k_proj.weight"),
        "wv": mats("layers.{}.self_attn.v_proj.weight"),
        "wo": mats("layers.{}.self_attn.dense.weight"),
        "bq": vecs("layers.{}.self_attn.q_proj.bias"),
        "bk": vecs("layers.{}.self_attn.k_proj.bias"),
        "bv": vecs("layers.{}.self_attn.v_proj.bias"),
        "bo": vecs("layers.{}.self_attn.dense.bias"),
        "w_up": mats("layers.{}.mlp.fc1.weight"),
        "b_up": vecs("layers.{}.mlp.fc1.bias"),
        "w_down": mats("layers.{}.mlp.fc2.weight"),
        "b_down": vecs("layers.{}.mlp.fc2.bias"),
    }
    return {
        "embed": jnp.asarray(to_np(sd[pre + "embed_tokens.weight"]), dt),
        "layers": layers,
        "final_norm_scale": jnp.asarray(
            to_np(sd[pre + "final_layernorm.weight"]), dt
        ),
        "final_norm_bias": jnp.asarray(
            to_np(sd[pre + "final_layernorm.bias"]), dt
        ),
        "lm_head": jnp.asarray(to_np(sd["lm_head.weight"]).T, dt),
        "lm_head_bias": jnp.asarray(to_np(sd["lm_head.bias"]), dt),
    }

"""Qwen2 model family — beyond the reference zoo (reference ships
llama/opt/falcon/mpt/starcoder, ``python/flexflow/serve/models``; Qwen2
is the same decoder recipe the zoo's generic engine already speaks:
RMSNorm + RoPE + GQA + SwiGLU, plus Q/K/V *biases* — the one knob that
distinguishes it from LLaMA). Runs on the generic decoder
(:mod:`.transformer`)."""
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
from .hf_utils import layer_stackers, linear_w, stack, to_np


def config(**kw) -> DecoderConfig:
    d: Dict[str, Any] = dict(
        vocab_size=151936,
        hidden_size=3584,
        intermediate_size=18944,
        num_hidden_layers=28,
        num_attention_heads=28,
        num_key_value_heads=4,
        max_position_embeddings=32768,
        norm_type="rmsnorm",
        norm_bias=False,
        norm_eps=1e-6,
        positions="rope",
        rope_theta=1000000.0,
        activation="silu",
        glu=True,
        parallel_block=False,
        qkv_bias=True,      # Qwen2's signature deviation from LLaMA
        out_bias=False,
        mlp_bias=False,
        tie_word_embeddings=False,
    )
    d.update(kw)
    return DecoderConfig(**d)


def qwen2_7b(**kw) -> DecoderConfig:
    return config(**kw)


def tiny(**kw) -> DecoderConfig:
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
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> DecoderConfig:
    if hf.get("model_type", "qwen2") != "qwen2":
        # qwen2_moe has its own family (models/qwen2_moe.py) and the
        # detect_family fallback matches longest-key-first, so only
        # genuinely unsupported variants (qwen2_vl etc.) land here —
        # their weights don't fit the dense decoder; fail with the
        # real reason
        raise NotImplementedError(
            f"model_type {hf['model_type']!r} is not dense Qwen2 "
            "(use the qwen2_moe family for MoE; VL is unsupported)"
        )
    if hf.get("use_sliding_window"):
        # the generic decoder runs full causal attention — silently
        # loading a sliding-window checkpoint would diverge from HF
        # beyond the window instead of erroring here
        raise NotImplementedError(
            "Qwen2 sliding-window attention (use_sliding_window=true) is "
            "not supported; load a full-attention checkpoint"
        )
    d = dict(
        vocab_size=hf.get("vocab_size", 151936),
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf.get(
            "num_key_value_heads", hf["num_attention_heads"]
        ),
        max_position_embeddings=hf.get("max_position_embeddings", 32768),
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        rope_theta=hf.get("rope_theta", 1000000.0),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
    )
    d.update(kw)
    return config(**d)


def convert_hf_state_dict(sd: Dict[str, Any], cfg: DecoderConfig) -> Dict[str, Any]:
    """HF ``Qwen2ForCausalLM`` state dict → framework pytree (stacked
    layer dim; HF linear weights transposed to (in, out) by linear_w)."""
    dt = cfg.dtype
    L = cfg.num_hidden_layers
    pre = "model."

    mats, vecs = layer_stackers(sd, pre, L, dt)

    layers = {
        "attn_norm_scale": vecs("layers.{}.input_layernorm.weight"),
        "mlp_norm_scale": vecs("layers.{}.post_attention_layernorm.weight"),
        "wq": mats("layers.{}.self_attn.q_proj.weight"),
        "wk": mats("layers.{}.self_attn.k_proj.weight"),
        "wv": mats("layers.{}.self_attn.v_proj.weight"),
        "bq": vecs("layers.{}.self_attn.q_proj.bias"),
        "bk": vecs("layers.{}.self_attn.k_proj.bias"),
        "bv": vecs("layers.{}.self_attn.v_proj.bias"),
        "wo": mats("layers.{}.self_attn.o_proj.weight"),
        "w_gate": mats("layers.{}.mlp.gate_proj.weight"),
        "w_up": mats("layers.{}.mlp.up_proj.weight"),
        "w_down": mats("layers.{}.mlp.down_proj.weight"),
    }
    params = {
        "embed": jnp.asarray(to_np(sd[pre + "embed_tokens.weight"]), dt),
        "layers": layers,
        "final_norm_scale": jnp.asarray(to_np(sd[pre + "norm.weight"]), dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(linear_w(sd, "lm_head.weight"), dt)
    return params

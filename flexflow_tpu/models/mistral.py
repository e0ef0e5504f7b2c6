"""Mistral model family — LLaMA-architecture dense decoder with
sliding-window attention (HF ``MistralForCausalLM``), beyond the
reference zoo (``inference/models/*`` has no Mistral and no windowed
attention). Runs on the generic decoder (:mod:`.transformer`) with
``sliding_window`` > 0: queries attend only the last w key positions;
training masks and the serving cache masks both enforce it."""
from __future__ import annotations

from typing import Any, Dict

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
from .llama import convert_hf_state_dict  # noqa: F401  (HF llama layout)


def config(**kw) -> DecoderConfig:
    d: Dict[str, Any] = dict(
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=8,
        max_position_embeddings=4096,
        norm_type="rmsnorm",
        norm_bias=False,
        norm_eps=1e-5,
        positions="rope",
        rope_theta=10000.0,
        activation="silu",
        glu=True,
        qkv_bias=False,
        out_bias=False,
        mlp_bias=False,
        tie_word_embeddings=False,
        sliding_window=4096,
    )
    d.update(kw)
    return DecoderConfig(**d)


def mistral_7b(**kw) -> DecoderConfig:
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
        sliding_window=8,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> DecoderConfig:
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
        norm_eps=hf.get("rms_norm_eps", 1e-5),
        rope_theta=hf.get("rope_theta", 10000.0),
        # null/absent window (mistral-v0.3-style configs) = full causal
        sliding_window=hf.get("sliding_window") or 0,
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
    )
    d.update(kw)
    return config(**d)

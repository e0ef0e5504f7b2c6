"""Falcon model family (reference ``inference/models/falcon.cc`` and
``python/flexflow/serve/models/falcon.py``): RoPE + MQA/GQA, *parallel*
attention+MLP blocks (one shared input LayerNorm on 7B, separate
ln_attn/ln_mlp on the 40B "new decoder architecture"), un-biased GELU
FFN. Runs on the generic decoder (:mod:`.transformer`)."""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp
import numpy as np

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
from .hf_utils import linear_w, stack, to_np


def config(**kw) -> DecoderConfig:
    d: Dict[str, Any] = dict(
        vocab_size=65024,
        hidden_size=4544,
        intermediate_size=4 * 4544,
        num_hidden_layers=32,
        num_attention_heads=71,
        num_key_value_heads=1,  # falcon-7b is MQA
        max_position_embeddings=2048,
        norm_type="layernorm",
        norm_bias=True,
        norm_eps=1e-5,
        positions="rope",
        activation="gelu",
        glu=False,
        parallel_block=True,
        parallel_two_norms=False,
        qkv_bias=False,
        out_bias=False,
        mlp_bias=False,
        tie_word_embeddings=False,
    )
    d.update(kw)
    return DecoderConfig(**d)


def falcon_7b(**kw) -> DecoderConfig:
    return config(**kw)


def tiny(**kw) -> DecoderConfig:
    d = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=1,
        max_position_embeddings=128,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> DecoderConfig:
    new_arch = hf.get("new_decoder_architecture", False)
    heads = hf.get("num_attention_heads", hf.get("n_head"))
    if new_arch:
        kv = hf.get("num_kv_heads", hf.get("n_head_kv", heads))
    elif hf.get("multi_query", True):
        kv = 1
    else:
        kv = heads
    d = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf.get("ffn_hidden_size", 4 * hf["hidden_size"]),
        num_hidden_layers=hf.get("num_hidden_layers", hf.get("n_layer")),
        num_attention_heads=heads,
        num_key_value_heads=kv,
        norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        rope_theta=hf.get("rope_theta", 10000.0),
        parallel_two_norms=new_arch,
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
    )
    d.update(kw)
    return config(**d)


def _split_fused_qkv(w: np.ndarray, cfg: DecoderConfig, new_arch: bool):
    """HF Falcon fuses QKV into one matmul. Old (7B, MQA) layout stacks
    all H query heads then 1 K and 1 V head; new (40B) layout interleaves
    per KV group: [G query heads, k, v] × KV. ``w`` is already (in, out)."""
    D = cfg.hidden_size
    H, KV, dk = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    if new_arch:
        g = w.reshape(D, KV, H // KV + 2, dk)
        wq = g[:, :, :-2, :].reshape(D, H * dk)
        wk = g[:, :, -2, :].reshape(D, KV * dk)
        wv = g[:, :, -1, :].reshape(D, KV * dk)
    else:
        g = w.reshape(D, H + 2 * KV, dk)
        wq = g[:, :H, :].reshape(D, H * dk)
        wk = g[:, H : H + KV, :].reshape(D, KV * dk)
        wv = g[:, H + KV :, :].reshape(D, KV * dk)
    return wq, wk, wv


def convert_hf_state_dict(sd: Dict[str, Any], cfg: DecoderConfig) -> Dict[str, Any]:
    """HF ``FalconForCausalLM`` state dict → framework pytree."""
    dt = cfg.dtype
    pre = "transformer."
    L = cfg.num_hidden_layers
    new_arch = cfg.parallel_two_norms

    wq, wk, wv = [], [], []
    for i in range(L):
        q, k, v = _split_fused_qkv(
            linear_w(sd, f"{pre}h.{i}.self_attention.query_key_value.weight"),
            cfg,
            new_arch,
        )
        wq.append(q), wk.append(k), wv.append(v)

    def vec(fmt):
        return stack([to_np(sd[pre + fmt.format(i)]) for i in range(L)], dt)

    if new_arch:
        norm = {
            "attn_norm_scale": vec("h.{}.ln_attn.weight"),
            "attn_norm_bias": vec("h.{}.ln_attn.bias"),
            "mlp_norm_scale": vec("h.{}.ln_mlp.weight"),
            "mlp_norm_bias": vec("h.{}.ln_mlp.bias"),
        }
    else:
        norm = {
            "attn_norm_scale": vec("h.{}.input_layernorm.weight"),
            "attn_norm_bias": vec("h.{}.input_layernorm.bias"),
        }

    layers = {
        **norm,
        "wq": stack(wq, dt),
        "wk": stack(wk, dt),
        "wv": stack(wv, dt),
        "wo": stack(
            [linear_w(sd, f"{pre}h.{i}.self_attention.dense.weight") for i in range(L)], dt
        ),
        "w_up": stack(
            [linear_w(sd, f"{pre}h.{i}.mlp.dense_h_to_4h.weight") for i in range(L)], dt
        ),
        "w_down": stack(
            [linear_w(sd, f"{pre}h.{i}.mlp.dense_4h_to_h.weight") for i in range(L)], dt
        ),
    }
    params = {
        "embed": jnp.asarray(to_np(sd[pre + "word_embeddings.weight"]), dt),
        "layers": layers,
        "final_norm_scale": jnp.asarray(to_np(sd[pre + "ln_f.weight"]), dt),
        "final_norm_bias": jnp.asarray(to_np(sd[pre + "ln_f.bias"]), dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(linear_w(sd, "lm_head.weight"), dt)
    return params

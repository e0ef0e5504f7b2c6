"""MPT model family (reference ``inference/models/mpt.cc`` and
``python/flexflow/serve/models/mpt.py``): ALiBi attention bias (no
positional embeddings), bias-free LayerNorm, un-biased MHA + GELU FFN,
tied LM head. Runs on the generic decoder (:mod:`.transformer`); the
ALiBi path adds a per-line position buffer to the KV cache so serving
bias is computed against true key positions (see
``transformer.needs_pos_cache``)."""
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
from .hf_utils import linear_w, stack, to_np


def config(**kw) -> DecoderConfig:
    d: Dict[str, Any] = dict(
        vocab_size=50368,
        hidden_size=4096,
        intermediate_size=4 * 4096,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=32,
        max_position_embeddings=2048,
        norm_type="layernorm",
        norm_bias=False,
        norm_eps=1e-5,
        positions="alibi",
        activation="gelu",
        glu=False,
        parallel_block=False,
        qkv_bias=False,
        out_bias=False,
        mlp_bias=False,
        tie_word_embeddings=True,
    )
    d.update(kw)
    return DecoderConfig(**d)


def mpt_7b(**kw) -> DecoderConfig:
    return config(**kw)


def tiny(**kw) -> DecoderConfig:
    d = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        max_position_embeddings=128,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> DecoderConfig:
    d = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["d_model"],
        intermediate_size=hf.get("expansion_ratio", 4) * hf["d_model"],
        num_hidden_layers=hf["n_layers"],
        num_attention_heads=hf["n_heads"],
        num_key_value_heads=hf["n_heads"],
        max_position_embeddings=hf.get("max_seq_len", 2048),
    )
    d.update(kw)
    return config(**d)


def convert_hf_state_dict(sd: Dict[str, Any], cfg: DecoderConfig) -> Dict[str, Any]:
    """HF ``MptForCausalLM`` state dict → framework pytree. The fused
    ``Wqkv`` (3D, D) splits into equal Q/K/V thirds."""
    dt = cfg.dtype
    pre = "transformer."
    L = cfg.num_hidden_layers
    D = cfg.hidden_size

    wq, wk, wv = [], [], []
    for i in range(L):
        w = linear_w(sd, f"{pre}blocks.{i}.attn.Wqkv.weight")  # (D, 3D)
        wq.append(w[:, :D])
        wk.append(w[:, D : 2 * D])
        wv.append(w[:, 2 * D :])

    def vec(fmt):
        return stack([to_np(sd[pre + fmt.format(i)]) for i in range(L)], dt)

    layers = {
        "attn_norm_scale": vec("blocks.{}.norm_1.weight"),
        "wq": stack(wq, dt),
        "wk": stack(wk, dt),
        "wv": stack(wv, dt),
        "wo": stack(
            [linear_w(sd, f"{pre}blocks.{i}.attn.out_proj.weight") for i in range(L)], dt
        ),
        "mlp_norm_scale": vec("blocks.{}.norm_2.weight"),
        "w_up": stack(
            [linear_w(sd, f"{pre}blocks.{i}.ffn.up_proj.weight") for i in range(L)], dt
        ),
        "w_down": stack(
            [linear_w(sd, f"{pre}blocks.{i}.ffn.down_proj.weight") for i in range(L)], dt
        ),
    }
    return {
        "embed": jnp.asarray(to_np(sd[pre + "wte.weight"]), dt),
        "layers": layers,
        "final_norm_scale": jnp.asarray(to_np(sd[pre + "norm_f.weight"]), dt),
    }

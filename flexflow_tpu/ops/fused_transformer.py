"""Fused transformer decoder stack as ONE graph-IR operator.

This op is the bridge between the graph-IR training stack (FFModel +
Unity search) and the fast hand-sharded path (models/transformer.py): the
whole N-layer decoder — RMSNorm → QKV+RoPE → attention → residual →
SwiGLU FFN, scanned over stacked layer weights with per-block remat and
optionally the Pallas flash-attention kernel — executes as a single op
inside ``FFModel.run_graph``. The Unity search prices and shards it like
any other node, so ``compile(auto_parallel=True)`` now reaches the same
compiled program quality as ``transformer.make_train_step`` instead of the
interpreted per-op graph.

The reference gets the equivalent effect from its FusedOp + the
substitution rules that pack a transformer block into fused operators
(reference ``src/ops/fused.cc``, ``graph_subst_3_v2.json`` transformer
rules); on TPU the fusion *inside* the op is XLA's job — what this op
contributes is scan-over-layers (compile time independent of depth),
``jax.checkpoint`` remat, and the flash-attention kernel, none of which
the per-op graph interpretation can express.

Sharding: the ``TP_MEGATRON`` strategy state maps to the classic
Megatron layout (QKV/up column-parallel, O/down row-parallel on the
``model`` axis; GSPMD inserts the two per-layer all-reduces). Input and
output activations are batch-sharded full-feature tensors, so from the
search's resharding point of view the op behaves like a DP node.
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.tensor import TensorSpec
from .. import initializers as ffinit  # noqa: F401  (kept for API symmetry)
from .registry import OpDef, register


def _cfg_from_attrs(attrs: Dict, D: int, S: int, dtype):
    from ..models import llama

    H = attrs["num_heads"]
    return llama.LLaMAConfig(
        vocab_size=1,  # unused: embed/head live outside this op
        hidden_size=D,
        intermediate_size=attrs["intermediate_size"],
        num_hidden_layers=attrs["num_layers"],
        num_attention_heads=H,
        num_key_value_heads=attrs.get("num_kv_heads") or H,
        norm_eps=attrs.get("eps", 1e-6),
        rope_theta=attrs.get("rope_theta", 10000.0),
        max_position_embeddings=max(S, 1),
        dtype=dtype,
    )


@register
class TransformerDecoderStackOp(OpDef):
    """N fused decoder blocks over (B, S, D) hidden states.

    attrs: num_layers, num_heads, num_kv_heads (None = MHA),
    intermediate_size, eps, rope_theta, remat (default True), attention
    ("xla" | "flash" — the Pallas kernel, ops/flash_attention.py).
    """

    type = "transformer_decoder_stack"

    def infer(self, in_specs: List[TensorSpec], attrs: Dict) -> List[TensorSpec]:
        (x,) = in_specs
        assert x.ndim == 3, "decoder stack input must be (B, S, D)"
        D, H = x.shape[-1], attrs["num_heads"]
        assert D % H == 0, f"hidden {D} not divisible by heads {H}"
        kv = attrs.get("num_kv_heads") or H
        assert H % kv == 0, f"heads {H} not divisible by kv heads {kv}"
        return [x]

    def init(self, key, in_specs: List[TensorSpec], attrs: Dict) -> Dict:
        from ..models import transformer

        (x,) = in_specs
        cfg = _cfg_from_attrs(attrs, x.shape[-1], x.shape[1], x.jnp_dtype)
        # init_params builds embed/head too (tiny at vocab_size=1);
        # keep only the stacked layer weights this op owns.
        full = transformer.init_params(key, cfg)
        return full["layers"]

    def forward(self, weights, inputs, attrs, ctx):
        from ..models import transformer

        (x,) = inputs
        B, S, D = x.shape
        cfg = _cfg_from_attrs(attrs, D, S, x.dtype)
        attn_fn = (transformer.make_flash_attention()
                   if attrs.get("attention", "xla") == "flash" else None)
        rope, bias, mask = transformer._full_sequence_context(
            cfg, jnp.arange(S, dtype=jnp.int32), attn_fn)
        blk = transformer._block_fn(
            cfg, attn_fn, attrs.get("remat", True), attrs.get("remat_policy"))

        def body(carry, p_l):
            y, _ = blk(p_l, carry, rope, bias, mask)
            return y, None

        y, _ = lax.scan(body, x, weights)
        return [y]

    # -- search/sharding hooks -----------------------------------------

    def weight_pspecs(self, in_specs, attrs, model_axis):
        if attrs.get("tp_shard") == "megatron":
            return {
                "attn_norm_scale": P(None, None),
                "wq": P(None, None, model_axis),
                "wk": P(None, None, model_axis),
                "wv": P(None, None, model_axis),
                "wo": P(None, model_axis, None),
                "mlp_norm_scale": P(None, None),
                "w_gate": P(None, None, model_axis),
                "w_down": P(None, model_axis, None),
                "w_up": P(None, None, model_axis),
            }
        return super().weight_pspecs(in_specs, attrs, model_axis)

    def flops(self, in_specs, attrs):
        (x,) = in_specs
        B, S, D = x.shape
        L, H = attrs["num_layers"], attrs["num_heads"]
        kv = attrs.get("num_kv_heads") or H
        dk = D // H
        F = attrs["intermediate_size"]
        per_layer_params = (
            D * (H * dk) + 2 * D * (kv * dk) + (H * dk) * D + 3 * D * F
        )
        # 2 FLOPs per param per token + the S-quadratic attention term
        return B * S * (2 * L * per_layer_params + 4 * L * D * S)

    def activation_bytes(self, in_specs, attrs, training: bool) -> float:
        """Live activation bytes for the memory model: with full
        per-block remat only the L inter-block boundaries are saved for
        backward (plus one block's working set, dominated by the
        boundaries for realistic L). The "dots" policy additionally
        keeps every matmul output, so its footprint is modelled like
        no-remat (a conservative upper bound — softmax/norm
        intermediates are the recomputed part)."""
        (x,) = in_specs
        xb = float(x.size_bytes)
        if not training:
            return xb
        full_remat = attrs.get("remat", True) and not attrs.get("remat_policy")
        if full_remat:
            return (attrs["num_layers"] + 1) * xb
        # no remat / dots policy: blocks keep hidden + qkv + ffn dots
        F = attrs["intermediate_size"]
        D = x.shape[-1]
        return attrs["num_layers"] * xb * (4 + 2 * F / D)

    def internal_collectives(self, in_specs, attrs, state: str, training: bool):
        """Per-step collectives GSPMD inserts *inside* this op under the
        given sharding state: Megatron TP pays one all-reduce of the
        (per-data-shard) activation after attention and one after the
        FFN per layer, and the backward pass mirrors both."""
        if state != "TP_MEGATRON":
            return []
        (x,) = in_specs
        act_bytes = float(x.size_bytes)
        per_layer = 2 * (2 if training else 1)
        return [("all_reduce", act_bytes)] * (per_layer * attrs["num_layers"])

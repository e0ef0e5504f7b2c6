"""Qwen3-Next model family (Qwen, ``model_type: qwen3_next``; Hugging
Face ``Qwen3Next*``): a pre-norm decoder whose layers have one of TWO
mixers — layer ``i`` is ``full_attention`` where ``(i + 1) %
full_attention_interval == 0`` and ``linear_attention`` elsewhere
(three to one as published) — and a sparse FFN every layer. Every norm
but one scales by ``1 + w`` (zero-centred), in float32.

* ``linear_attention``: a Gated DeltaNet layer with FEWER KEY HEADS
  THAN VALUE HEADS (16 for 32 as published): ``[q | k | v | z] = h
  W_qkvz``; q, k and v through ONE depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps and SiLU; q and k L2-normalised a
  head, q scaled by ``dk^-0.5``, each key head the q and k of its group
  of value heads; ``[b | a] = h W_ba``, ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``. A value head keeps a (dk, dv)
  float32 state a request under the gated delta rule, which is
  ``models/olmo_hybrid.py``'s (``delta_mixer``: the rule, its chunk
  form, ``serve/kernels.gdn_recur_c1``; ONE copy for both families).
  The output is ``(rmsnorm_dv(o) * w_o_norm * silu(z)) W_o`` (this norm
  scales by ``w``).
* ``full_attention``: grouped-query softmax attention at head size 256
  whose query projection carries an OUTPUT GATE a head (a head's
  columns of ``wq``: its query, then its gate): ``o * sigmoid(gate)``
  before ``wo``. q and k take an RMSNorm a head (``1 + w``) and rope on
  the first ``partial_rotary_factor`` of the head (``rotary_pct``).
* the sparse block: a softmax router over ``num_experts`` outputs, the
  ``num_experts_per_tok`` largest renormalised
  (``transformer.route_softmax_topk``), SiLU-gated experts through
  ``transformer.routed_experts_ffn``, and beside them ONE shared expert
  times ``sigmoid(h w_sg)``. ``experts_held`` (a range of the router's
  outputs, all of them unless told) is the guide's usual cut: the
  weights hold that range only and the layer computes that range's
  part, the shared expert whole.

The equations are written out in ``benchmarks/references/qwen3_next.py``
(the recurrence token by token), which the tests hold this file to. No
multi-token-prediction module: ordinary decoding does not use it.

Serving only, on the paged path (``kv_layout="paged"``), through the
engine's ordinary step programs, as ``models/olmo_hybrid.py``: the
layer loop is :func:`transformer.run_layers`; the cache is the paged
K/V pool of the FULL layers only (``k``/``v``: (full layers, pages+1,
page, KV * d), a line's two heads merged on the minor axis) plus
per-SLOT state (``SLOT_STATE``): ``state`` (recurrent layers, slots,
value heads / p, dk, p dv) float32 (p = ``olmo_hybrid.lane_pack``: 1 at
dv = 128) and ``conv`` (recurrent layers, taps - 1, slots, channels) in
the cache's dtype; the step takes the engine's PACKED token axis and
returns each layer's real tokens per expert held (``step_counts``).

What it refuses, at construction (``validate_serving``), each because
the per-slot state has no such operation yet: prefix caching, SpecInfer
and beam search, ``kv_quant``, ``fused_decode``, ``kv_shard="context"``,
the dense layout, a mesh with ``model > 1``.

Weight names follow ``benchmarks/harness/model.py::make_params``' rule
(a leaf whose name holds ``norm_scale`` is drawn one, ``bias`` or a
leading ``b`` zero, ``wo`` and ``w_down`` at the residual's std): the
zero-centred norms' weights are ``*_norm_w`` (``attn_norm_w`` is HF's
``input_layernorm``, ``mlp_norm_w`` its ``post_attention_layernorm``),
so that a draw leaves ``1 + w`` near one; ``o_norm_scale`` is the
recurrent layer's output norm; ``w_gates`` holds W_ba ((D, 2 value
heads): the write strengths' columns first); the shared expert is the
nested group ``shared`` (``w_gate`` / ``w_up`` / ``w_down``) and
``w_shared_gate`` its (D, 1) gate. The published checkpoint interleaves
``W_qkvz`` and ``W_ba`` a key head; here they are plain column blocks
(a converter would permute the columns).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..obs.sublayers import sublayer
from .olmo_hybrid import _write_lines, delta_mixer, lane_pack, step_context
from .transformer import (
    DecoderConfig,
    _embed_in,
    _ffn,
    _gather_attended,
    _head_logits,
    _layer_of,
    _mm,
    _norm,
    _pallas_pools,
    _serve_attend,
    _spread_queries,
    apply_rope,
    layer_weights,
    rope_freqs,
    route_softmax_topk,
    routed_experts_ffn,
    run_layers,
    seeded_normal,
)

LINEAR, ATTENTION = "linear_attention", "full_attention"
# the cache entries that are per SLOT, not per page
SLOT_STATE = ("state", "conv")
# the one of them a real token updates by a recurrence
# (SchedulerStats.recurrent_updates)
RECURRENT_STATE = "state"
FUSED_DECODE = ()
PACKED_STEP = True


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig(DecoderConfig):
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512               # the router's outputs
    shared_expert_intermediate_size: int = 512
    # the range of the router's outputs whose experts' weights are here
    # ((0, 0): all of them)
    experts_held: Tuple[int, int] = (0, 0)
    # slots of per-slot state where ``init_paged_kv_cache`` is not told
    # (``benchmarks/tools/fit.py``; the engine always tells)
    state_slots: int = 0

    def __post_init__(self):
        super().__post_init__()
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"{self.linear_num_value_heads} value heads are no whole "
                f"groups of {self.linear_num_key_heads} key heads")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of {self.num_experts}")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held if any(self.experts_held) else (0, self.num_experts)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        every = self.full_attention_interval
        return tuple(ATTENTION if (i + 1) % every == 0 else LINEAR
                     for i in range(self.num_hidden_layers))

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """A layer's kind: (mixer group, FFN group)."""
        return tuple(("gdn" if t == LINEAR else "attn", "sparse")
                     for t in self.layer_types)

    def count(self, group: str) -> int:
        return sum(group in kind for kind in self.kinds)

    @property
    def gdn_heads(self) -> Tuple[int, int, int, int]:
        """(key heads, value heads, dk, dv) of a recurrent layer."""
        return (self.linear_num_key_heads, self.linear_num_value_heads,
                self.linear_key_head_dim, self.linear_value_head_dim)

    @property
    def conv_dim(self) -> int:
        """Channels of a recurrent layer's convolution: q, k and v."""
        Hk, H, dk, dv = self.gdn_heads
        return 2 * Hk * dk + H * dv


def config(**kw) -> Qwen3NextConfig:
    d: Dict[str, Any] = dict(
        vocab_size=151936, hidden_size=2048, intermediate_size=5120,
        moe_intermediate_size=512, num_hidden_layers=48,
        num_attention_heads=16, num_key_value_heads=2, head_dim_override=256,
        max_position_embeddings=262144, norm_type="rmsnorm", norm_bias=False,
        norm_eps=1e-6, norm_plus_one=True, positions="rope", rope_theta=1e7,
        rotary_pct=0.25, activation="silu", glu=True,
        tie_word_embeddings=False, num_experts_per_tok=10, moe_norm_topk=True,
    )
    d.update(kw)
    return Qwen3NextConfig(**d)


def tiny(**kw) -> Qwen3NextConfig:
    """CPU test size: one period of the layer pattern (a run of three
    recurrent layers, a full layer); 2 key heads for 4 value heads; 4 /
    2 softmax heads of 32 with 8 channels rotated; 16 experts, 3 a
    token."""
    d = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim_override=32, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16, num_experts=16,
        num_experts_per_tok=3, max_position_embeddings=512,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> Qwen3NextConfig:
    """From the published ``config.json`` keys, as they are spelled. A
    benchmark configuration that cuts the experts gives ``num_experts``
    as the count held, the range as ``experts_held`` ([lo, hi]) and the
    router's width as ``router_outputs``; the published file has neither
    key and its ``num_experts`` is the router's width, every expert
    held. What is not built is refused."""
    if hf.get("mlp_only_layers"):
        raise NotImplementedError(
            f"mlp_only_layers {hf['mlp_only_layers']}: every layer's FFN is "
            "the sparse block here, as published")
    if hf.get("decoder_sparse_step", 1) != 1:
        raise NotImplementedError(
            f"decoder_sparse_step {hf['decoder_sparse_step']}: every layer "
            "is sparse, as published")
    if hf.get("rope_scaling") is not None:
        raise NotImplementedError(f"rope_scaling {hf['rope_scaling']}")
    if hf.get("use_sliding_window"):
        raise NotImplementedError(
            "use_sliding_window: the full layers attend the whole context, "
            "as published")
    if hf.get("attention_bias"):
        raise NotImplementedError("attention_bias: the published model has none")
    if hf.get("hidden_act", "silu") != "silu":
        raise NotImplementedError(f"hidden_act {hf['hidden_act']!r}")
    held = tuple(hf.get("experts_held", (0, 0)))
    if any(held) and held[1] - held[0] != hf["num_experts"]:
        raise ValueError(
            f"experts_held {held} is not the {hf['num_experts']} experts "
            "num_experts counts")
    heads = kw.get("num_attention_heads", hf["num_attention_heads"])
    hidden = kw.get("hidden_size", hf["hidden_size"])
    d = dict(
        vocab_size=hf["vocab_size"], hidden_size=hidden,
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_expert_intermediate_size=hf["shared_expert_intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"], num_attention_heads=heads,
        num_key_value_heads=hf["num_key_value_heads"],
        head_dim_override=hf.get("head_dim") or hidden // heads,
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        rope_theta=float(hf.get("rope_theta", 1e7)),
        rotary_pct=float(hf.get("partial_rotary_factor", 0.25)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        full_attention_interval=hf.get("full_attention_interval", 4),
        linear_num_key_heads=hf["linear_num_key_heads"],
        linear_num_value_heads=hf["linear_num_value_heads"],
        linear_key_head_dim=hf["linear_key_head_dim"],
        linear_value_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel_dim=hf["linear_conv_kernel_dim"],
        num_experts=hf.get("router_outputs", hf["num_experts"]),
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        experts_held=held,
        state_slots=int(hf.get("serving", {}).get("max_requests_per_batch", 0)),
    )
    d.update(kw)
    return config(**d)


# ---------------------------------------------------------------------------
# Parameters: three stacked groups (two mixers, the sparse FFN) and the ends


def _group_shapes(cfg: Qwen3NextConfig, group: str) -> Dict[str, Any]:
    D = cfg.hidden_size
    if group == "gdn":
        _, H, _, dv = cfg.gdn_heads
        return {"attn_norm_w": (D,), "w_qkvz": (D, cfg.conv_dim + H * dv),
                "conv_w": (cfg.linear_conv_kernel_dim, cfg.conv_dim),
                "w_gates": (D, 2 * H), "dt_bias": (H,), "A_log": (H,),
                "o_norm_scale": (dv,), "wo": (H * dv, D)}
    if group == "attn":
        H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        return {"attn_norm_w": (D,), "wq": (D, H * 2 * d), "wk": (D, KV * d),
                "wv": (D, KV * d), "q_norm_w": (d,), "k_norm_w": (d,),
                "wo": (H * d, D)}
    F, S = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    n = cfg.held[1] - cfg.held[0]
    return {"mlp_norm_w": (D,), "w_router": (D, cfg.num_experts),
            "w_gate": (n, D, F), "w_up": (n, D, F), "w_down": (n, F, D),
            "shared": {"w_gate": (D, S), "w_up": (D, S), "w_down": (S, D)},
            "w_shared_gate": (D, 1)}


GROUPS = ("gdn", "attn", "sparse")


def init_params(key, cfg: Qwen3NextConfig) -> Dict[str, Any]:
    """The family's own draw: 0.02 (0.02 / sqrt(2 N) for ``wo`` and
    every ``w_down``); the zero-centred norms' ``w`` zero and the output
    norm's scale one (the published initialisation); the Gated DeltaNet
    layer's as ``olmo_hybrid.init_params``: ``A`` uniform in (0, 16),
    ``dt`` log-uniform in (0.001, 0.1) with ``dt_bias`` its inverse
    softplus, the taps at 1 / sqrt(taps)."""
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 64))

    def leaf(name, shape):
        if name.endswith("norm_w"):
            return jnp.zeros(shape, cfg.dtype)
        if "norm_scale" in name:
            return jnp.ones(shape, cfg.dtype)
        if name == "A_log":
            return jnp.log(jax.random.uniform(
                next(keys), shape, jnp.float32, 1e-3, 16.0))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                next(keys), shape, jnp.float32, math.log(1e-3), math.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        scale = {"wo": out_std, "w_down": out_std,
                 "conv_w": 1.0 / math.sqrt(cfg.linear_conv_kernel_dim)}.get(name, std)
        return seeded_normal(next(keys), scale, shape=shape, dtype=cfg.dtype)

    def group(shapes, n):
        return {name: group(s, n) if isinstance(s, dict) else leaf(name, (n,) + s)
                for name, s in shapes.items()}

    params = {
        "embed": leaf("embed", (cfg.vocab_size, cfg.hidden_size)),
        "final_norm_w": leaf("final_norm_w", (cfg.hidden_size,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = leaf("lm_head", (cfg.hidden_size, cfg.vocab_size))
    for name in GROUPS:
        if cfg.count(name):
            params[name] = group(_group_shapes(cfg, name), cfg.count(name))
    return params


def param_pspecs(cfg: Qwen3NextConfig, *, pipeline: bool = False) -> Dict[str, Any]:
    """Every weight whole on every device: one chip, or replicas
    (``validate_serving`` refuses ``model > 1``)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda _: P(), shapes)


def num_params(cfg: Qwen3NextConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def step_counts(cfg: Qwen3NextConfig) -> Dict[str, Tuple[int, ...]]:
    """What a step returns in its cache that is no state
    (``lfm2_moe.step_counts``). ``moe_counts``: each layer's real tokens
    per expert held."""
    return {"moe_counts": (cfg.count("sparse"), cfg.held[1] - cfg.held[0])}


def expert_routing(cfg: Qwen3NextConfig) -> Tuple[int, Tuple[int, int], int]:
    """(The experts a token chooses, the range of experts held, the
    router's outputs): ``transformer.expert_routing``'s contract."""
    return cfg.num_experts_per_tok, cfg.held, cfg.num_experts


def validate_serving(cfg: Qwen3NextConfig, serving, mesh, *, specinfer: bool = False) -> None:
    """The combinations this family's per-slot state cannot serve yet,
    refused at engine construction, each naming what is missing."""
    from ..core.mesh import MODEL_AXIS

    def refuse(what, why):
        raise NotImplementedError(f"qwen3_next does not serve {what}: {why}")

    if serving.kv_layout != "paged":
        refuse(f"kv_layout={serving.kv_layout!r}",
               "only the paged step carries the recurrent layers' states "
               "beside the pool")
    if serving.prefix_caching:
        refuse("prefix_caching=True",
               "pages can be shared between requests, a recurrent layer's "
               "state at a page boundary is not kept with them (no state "
               "snapshot yet)")
    if specinfer:
        refuse("SpecInfer or beam search",
               "commit_kv / reorder_slots would have to roll the per-slot "
               "recurrent state back to the accepted token, and no snapshot "
               "is kept")
    if serving.kv_quant is not None:
        refuse(f"kv_quant={serving.kv_quant!r}",
               "the full layers' pool has no scale rows in this family's "
               "cache")
    if serving.fused_decode:
        refuse(f"fused_decode={serving.fused_decode!r}",
               "the fused prologue knows one kind of layer and no output "
               "gate")
    if serving.kv_shard == "context":
        refuse(f"kv_shard={serving.kv_shard!r}",
               "the recurrent state of a row lives on one shard")
    if mesh.shape.get(MODEL_AXIS, 1) > 1:
        refuse("a mesh with model > 1",
               "neither the recurrent state nor the grouped expert matmul "
               "is sharded yet")


def _no_state_rollback(*_a, **_k):
    raise NotImplementedError(
        "qwen3_next keeps per-slot recurrent state: committing, copying or "
        "reordering cache lines would need that state rolled back or moved "
        "with them, and no snapshot is kept")


commit_kv_paged = reorder_slots_paged = copy_page_kv = _no_state_rollback
gather_page_kv = scatter_page_kv = _no_state_rollback
init_kv_cache = kv_cache_pspecs = serve_step = _no_state_rollback
commit_kv = reorder_slots = _no_state_rollback


# ---------------------------------------------------------------------------
# Cache: the full layers' paged pool, the recurrent layers' per-slot state


def init_paged_kv_cache(
    cfg: Qwen3NextConfig, num_pages: int, page_size: int, dtype=None,
    kv_quant: Optional[str] = None, extra_rows: int = 0, *,
    num_slots: Optional[int] = None, cache_len: Optional[int] = None,
):
    """``k``/``v``: (full layers, num_pages+1, page_size, KV * d), a
    line's heads MERGED on the minor axis (two heads are no sublane
    tile: a (..., page, 2, 256) array is held with its heads padded),
    row ``num_pages`` the scratch page; ``state``: (recurrent layers,
    slots, value heads / p, dk, p dv) float32 whatever the cache's
    dtype, laid out by ``olmo_hybrid.lane_pack`` (dv = 128: p = 1, a
    (128, 128) tile a head, 2.1 MB a layer and slot as published);
    ``conv``: (recurrent layers, taps - 1, slots, channels), each slot's
    newest convolution inputs, oldest first."""
    if kv_quant is not None or extra_rows:
        raise NotImplementedError(
            "qwen3_next's pool is neither quantized nor row-sharded "
            "(validate_serving refuses kv_quant and kv_shard='context')")
    slots = num_slots or cfg.state_slots
    if not slots:
        raise ValueError(
            "qwen3_next keeps per-slot state: init_paged_kv_cache needs "
            "num_slots (the engine passes its own)")
    dt = dtype or cfg.dtype
    pool = (cfg.count("attn"), num_pages + 1, page_size,
            cfg.num_key_value_heads * cfg.head_dim)
    n = cfg.count("gdn")
    _, H, dk, dv = cfg.gdn_heads
    p = lane_pack(H, dv)
    return {
        "k": jnp.zeros(pool, dt), "v": jnp.zeros(pool, dt),
        "state": jnp.zeros((n, slots, H // p, dk, p * dv), jnp.float32),
        "conv": jnp.zeros((n, cfg.linear_conv_kernel_dim - 1, slots,
                           cfg.conv_dim), dt),
    }


def paged_kv_cache_pspecs(cfg: Qwen3NextConfig = None, *, pipeline: bool = False,
                          kv_quant: Optional[str] = None,
                          kv_shard: Optional[str] = None):
    return {name: P() for name in ("k", "v") + SLOT_STATE}


# ---------------------------------------------------------------------------
# The blocks


def _gdn_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    B, T, D = x.shape
    _, H, _, dv = cfg.gdn_heads
    f32 = jnp.float32
    h = _norm(cfg, x, p["attn_norm_w"], None).reshape(B * T, D)
    with sublayer("mixer"):
        qkv, z = jnp.split(_mm(h, p["w_qkvz"]), (cfg.conv_dim,), axis=-1)
        o, carried = delta_mixer(ctx, carried, index, h, qkv, p,
                                 heads=cfg.gdn_heads, beta_scale=1.0)
        # the one norm that scales by w: a head's own dv values
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
        o = (o * p["o_norm_scale"].astype(f32)).astype(x.dtype)
        o = o * jax.nn.silu(z).reshape(-1, H, dv)
        out = _mm(o.reshape(B, T, H * dv), p["wo"])
    return x + out, carried


def gated_queries(cfg, p, h):
    """(q (..., H, d), gate (..., H, d)) of normed tokens h: a head's 2 d
    columns of ``wq`` are its query, then its output gate."""
    H, d = cfg.num_attention_heads, cfg.head_dim
    qg = _mm(h, p["wq"]).reshape(h.shape[:-1] + (H, 2 * d))
    return qg[..., :d], qg[..., d:]


def _attn_block(cfg, ctx, stack, index, x, carried):
    from ..serve import kernels as _pk

    p = layer_weights(stack, index)
    B, T, _ = x.shape
    H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    h = _norm(cfg, x, p["attn_norm_w"], None)
    with sublayer("attn.proj"):
        q, gate = gated_queries(cfg, p, h)
        q = _norm(cfg, q, p["q_norm_w"], None)
        k = _norm(cfg, _mm(h, p["wk"]).reshape(B, T, KV, d), p["k_norm_w"], None)
        v = _mm(h, p["wv"])
        q, k = apply_rope(q, *ctx["rope"]), apply_rope(k, *ctx["rope"])
    with sublayer("attn.write"):
        kp, vp = (_write_lines(pool, index, ctx["phys"], ctx["off"], lines)
                  for pool, lines in ((carried["k"], k.reshape(B, T, KV * d)),
                                      (carried["v"], v)))
    with sublayer("attn.core"):
        q = _spread_queries(q, ctx["pack"])                   # (R, C, H, d)
        if ctx["kernels"] == "pallas":
            k_rows, v_rows, kw = _pallas_pools(kp, vp, None, None, index)
            o = _pk.ragged_paged_attention(
                q, k_rows, v_rows, ctx["page_table"], ctx["mask"],
                row_offset=kw["row_offset"], q_len=ctx["q_len"],
                work=ctx["work"])
        else:
            k_virt, v_virt = (
                _pk.gather_pages(_layer_of(pool, index), ctx["page_table"])
                for pool in (kp, vp))
            split = k_virt.shape[:2] + (KV, d)
            o = _serve_attend(cfg, q, k_virt.reshape(split),
                              v_virt.reshape(split), None, ctx["mask"])
        o = _gather_attended(o, ctx["pack"])
    with sublayer("attn.proj"):
        o = o * jax.nn.sigmoid(gate.reshape(B, T, H * d))
        out = _mm(o, p["wo"])
    return x + out, dict(carried, k=kp, v=vp)


@sublayer("ffn")
def shared_expert(cfg, p, h):
    """The always-on expert times its token gate ``sigmoid(h w_sg)``
    (``transformer._shared_expert``'s form on this family's leaves)."""
    gate = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), p["w_shared_gate"].astype(jnp.float32),
        preferred_element_type=jnp.float32)).astype(h.dtype)
    return gate * _ffn(cfg, p["shared"], h)


def sparse_ffn(cfg, p, h, real, layer=None, kernels="xla"):
    """One layer's sparse block over a flat token axis: h (N, D) normed,
    ``real`` (N,). ``p``: the layer's router and shared-expert weights,
    and the routed experts' weights of the layer — or, with ``layer``,
    of every layer, stacked. The experts held compute their part, the
    shared expert the whole of its own.
    -> (out (N, D), counts (experts held,))."""
    experts, weights = route_softmax_topk(
        h, p["w_router"], cfg.num_experts_per_tok, norm_topk=cfg.moe_norm_topk)
    _, held, routed = expert_routing(cfg)
    out, counts = routed_experts_ffn(
        h, real, experts, weights, p["w_gate"], p["w_up"], p["w_down"],
        experts_held=held, routed=routed, layer=layer, kernels=kernels)
    return out + shared_expert(cfg, p, h), counts


def _sparse_block(cfg, ctx, stack, index, x, carried):
    routed = {k: v for k, v in stack.items() if k != "shared"}
    p = layer_weights(routed, index, whole=("w_gate", "w_up", "w_down"))
    p["shared"] = layer_weights(stack["shared"], index)
    B, T, D = x.shape
    h = _norm(cfg, x, p["mlp_norm_w"], None).reshape(B * T, D)
    out, counts = sparse_ffn(cfg, p, h, ctx["real"], layer=index,
                             kernels=ctx["kernels"])
    carried = dict(carried, moe_counts=lax.dynamic_update_index_in_dim(
        carried["moe_counts"], counts, index, 0))
    return x + out.reshape(B, T, D), carried


# ---------------------------------------------------------------------------
# The step


@sublayer("glue")
def serve_step_paged(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,      # (R, C)
    positions: jnp.ndarray,   # (R, C); the scratch position is padding
    logits_idx: jnp.ndarray,  # (R,)
    mask, cache_positions,
    page_table: jnp.ndarray,  # (R, NP) int32
    *,
    cfg: Qwen3NextConfig,
    cache_len: int,
    all_logits: bool = False,
    kernels: str = "xla",
    pack: Optional[int] = None,
    **unsupported,
):
    """The engine's paged step (models/transformer.serve_step_paged's
    contract, its packed token axis included) over the layer order. A
    row's real positions are its first columns, consecutive; a row
    whose first position is 0 starts from zero states
    (``models/olmo_hybrid.py``). The returned cache also holds
    ``moe_counts`` (``step_counts``: an output, not an input)."""
    if mask is not None or cache_positions is not None or any(
            v for v in unsupported.values()):
        _no_state_rollback()
    if pack is not None and all_logits:
        raise ValueError("a packed token axis returns one logits row a row")
    token_axis, ctx = step_context(
        tokens, positions, page_table, cache["k"].shape[2], cache_len,
        kernels, pack)
    with sublayer("attn.proj"):
        ctx["rope"] = rope_freqs(cfg, token_axis[1])
    # padding places (the scratch position) route nowhere
    ctx["real"] = token_axis[1].reshape(-1) < cache_len
    x = _embed_in(cfg, params, *token_axis)
    carried = dict(cache, **{name: jnp.zeros(shape, jnp.int32)
                             for name, shape in step_counts(cfg).items()})
    blocks = {
        name: functools.partial(fn, cfg, ctx)
        for name, fn in (("gdn", _gdn_block), ("attn", _attn_block),
                         ("sparse", _sparse_block))}
    x, new_cache = run_layers(cfg.kinds, blocks, params, x, carried)
    # the final norm is zero-centred like the blocks', under its own name
    head = dict(params, final_norm_scale=params["final_norm_w"])
    return _head_logits(cfg, head, x, logits_idx, ctx["pack"],
                        all_logits), new_cache

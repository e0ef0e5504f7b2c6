"""Olmo-Hybrid model family (allenai, ``model_type: olmo_hybrid``): a
dense decoder whose layers have one of TWO mixers, in the order
``layer_types`` gives (three ``linear_attention`` to one
``full_attention`` as published):

* ``linear_attention``: a Gated DeltaNet layer. ``[q' | k' | v'] = x
  W_qkv``, each channel through a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps and SiLU; q and k L2-normalised a
  head, q scaled by ``dk^-0.5``. A head keeps a (dk, dv) float32 state
  a request, updated a token by the gated delta rule: ``S <- a S``,
  ``u = b (v - S^T k)``, ``S <- S + k u^T``, ``o = S^T q``, with the
  decay ``a = exp(-exp(A_log) softplus(x W_a + dt_bias))`` and the
  write strength ``b = sigmoid(x W_b)`` (times 2 with
  ``linear_allow_neg_eigval``) computed from the token. The output is
  ``(rmsnorm_dv(o) * silu(x W_g)) W_o``. A decoding request carries the
  state and the last taps - 1 inputs of the convolution: per-slot
  state, no K/V.
* ``full_attention``: softmax attention over the paged K/V pool with an
  RMSNorm over the WHOLE q and k projections, and no rope.
* the block puts its norm AFTER each sublayer (Olmo 2 and 3):
  ``x += rmsnorm(mixer(x))``, ``x += rmsnorm(ffn(x))``; the FFN is
  SiLU-gated.

The equations are written out in ``benchmarks/references/olmo_hybrid.py``
(the recurrence token by token), which the tests hold this file to.

Serving only, on the paged path (``kv_layout="paged"``), through the
engine's ordinary step programs, as ``models/lfm2_moe.py``:

* the layer loop is :func:`transformer.run_layers`; the K/V pool of the
  attention layers and the recurrent layers' states are its carry,
  updated in place (tests/test_chip_compile.py).
* the cache is the paged K/V pool of the ATTENTION layers only
  (``k``/``v``: (attention layers, pages+1, page, KV * d), a line's
  heads merged on the minor axis as ``models/lfm2_moe.py``'s) plus per-SLOT
  state (``SLOT_STATE``): ``state`` (recurrent layers, slots, H / p, dk,
  p dv) float32, p = :func:`lane_pack` heads side by side on the minor
  axis so that it is whole lane tiles (published: two heads of 192 a
  row of 384; the device pads a minor extent of 192 to 256, a third
  more to hold and to move: ISSUE 48), and ``conv`` (recurrent layers,
  taps - 1, slots, 2 H dk + H dv): the q, k and v convolutions' newest
  inputs side by side, in the cache's dtype. The rule for any per-slot
  float32 state: its minor extent is a multiple of 128 or it is packed.
* what a step is handed decides everything: a row whose chunk starts at
  position 0 starts from zero states, the scratch position and padded
  rows update nothing (a row with no real token keeps its states
  bitwise), a chunk leaves the states of its last real token.
* the step takes the engine's PACKED token axis (``PACKED_STEP``). The
  delta rule runs a ROW at a time: :func:`gated_delta` is the
  recurrence itself for one token a row (the C = 1 step, and the rows
  of a mixed step that hold one token), written on the state as the
  cache holds it, and the chunk form for more (each row of a mixed
  step that prefills, in a loop over those rows alone: a mixed step
  holds one or two of them beside 60 that decode; that loop alone
  views a row's 2.2 MB with the heads apart). No program transposes a
  layer's state. The C = 1 program under ``kernels="pallas"`` runs the
  recurrence in ONE pass over the state (:func:`recurrence_c1`,
  ``serve/kernels.gdn_recur_c1``, ``ff_gdn_recur_c1``: XLA makes two
  fusions of the rule, each over the layer).
  The chunk form solves ``(I + A) U = rhs`` a sub-chunk of
  :data:`SUB_CHUNK` positions (a unit lower-triangular solve); every
  exponent in it is a difference ``G_i - G_j`` with j <= i taken on the
  masked triangle (``exp(-G)`` overflows float32 inside one chunk at a
  decay of 0.5 a token).

What it refuses, at construction (``validate_serving``), each because
the per-slot state has no such operation yet: prefix caching, SpecInfer
and beam search, ``kv_quant``, ``fused_decode``, ``kv_shard="context"``,
the dense layout, a mesh with ``model > 1``.

Weight names follow ``benchmarks/harness/model.py::make_params``' rule
(a leaf whose name holds ``bias`` or starts with ``b`` is drawn zero,
``norm_scale`` one, ``wo`` and ``w_down`` at the residual's std):
``w_gates`` holds W_b and W_a side by side ((D, 2 H): the write
strengths' columns first), ``dt_bias`` and ``A_log`` are float32 a
head, ``mixer_norm_scale`` / ``mlp_norm_scale`` the norms after the two
sublayers, ``o_norm_scale`` the (dv,) scale of the output norm.
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
from .lfm2_moe import short_conv
from .transformer import (
    DecoderConfig,
    _embed_in,
    _ffn,
    _gather_attended,
    _head_logits,
    _layer_of,
    _mm,
    _norm,
    _pack_tokens,
    _page_lookup,
    _pallas_pools,
    _serve_attend,
    _spread_queries,
    layer_weights,
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
HIGHEST = lax.Precision.HIGHEST
#: positions the chunk form solves at once
SUB_CHUNK = 64
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(DecoderConfig):
    layer_types: Tuple[str, ...] = ()
    linear_num_heads: int = 30           # value heads: a state each
    # key heads, each serving linear_num_heads / linear_num_key_heads
    # value heads' states (0: a key head a value head, as published)
    linear_num_key_heads: int = 0
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # slots of per-slot state where ``init_paged_kv_cache`` is not told
    # (``benchmarks/tools/fit.py``; the engine always tells)
    state_slots: int = 0

    def __post_init__(self):
        super().__post_init__()
        kinds = self.layer_types
        if len(kinds) != self.num_hidden_layers or set(kinds) - {LINEAR, ATTENTION}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {LINEAR!r} or {ATTENTION!r}: got {kinds}")
        if self.linear_num_heads % self.key_heads:
            raise ValueError(
                f"{self.linear_num_heads} value heads are no whole groups "
                f"of {self.key_heads} key heads")

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """A layer's kind: (mixer group, FFN group)."""
        return tuple(("gdn" if t == LINEAR else "attn", "ffn")
                     for t in self.layer_types)

    @property
    def key_heads(self) -> int:
        return self.linear_num_key_heads or self.linear_num_heads

    def count(self, group: str) -> int:
        return sum(group in kind for kind in self.kinds)

    @property
    def conv_dim(self) -> int:
        """Channels of a recurrent layer's convolutions: q, k and v."""
        return (2 * self.key_heads * self.linear_key_head_dim
                + self.linear_num_heads * self.linear_value_head_dim)


def config(**kw) -> OlmoHybridConfig:
    d: Dict[str, Any] = dict(
        vocab_size=100352, hidden_size=3840, intermediate_size=11008,
        num_hidden_layers=32, num_attention_heads=30, num_key_value_heads=30,
        max_position_embeddings=65536, norm_type="rmsnorm", norm_bias=False,
        norm_eps=1e-6, positions="none", activation="silu", glu=True,
        tie_word_embeddings=False,
    )
    d.update(kw)
    period = (LINEAR, LINEAR, LINEAR, ATTENTION)
    n = d["num_hidden_layers"]
    d.setdefault("layer_types", (period * -(-n // 4))[:n])
    return OlmoHybridConfig(**d)


def tiny(**kw) -> OlmoHybridConfig:
    """CPU test size: one published period and a recurrent layer after
    it (a run of three, a run of one); three heads, dk != dv."""
    d = dict(
        vocab_size=256, hidden_size=48, intermediate_size=96,
        num_hidden_layers=5, num_attention_heads=3, num_key_value_heads=3,
        linear_num_heads=3, linear_key_head_dim=8, linear_value_head_dim=16,
        max_position_embeddings=512,
        layer_types=(LINEAR, LINEAR, LINEAR, ATTENTION, LINEAR),
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> OlmoHybridConfig:
    """From the published ``config.json`` keys, as they are spelled.
    ``num_hidden_layers`` under ``len(layer_types)`` takes the first
    entries. ``head_dim`` is read where a configuration states it.
    ``linear_num_key_heads`` under ``linear_num_value_heads`` (the
    published file has them equal) gives every key head a group of
    value heads (:func:`gated_delta`)."""
    n = kw.get("num_hidden_layers", hf["num_hidden_layers"])
    if hf.get("attention_bias"):
        raise NotImplementedError("attention_bias: the published model has none")
    if (hf.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise NotImplementedError(
            "rope_theta: the published file's is null and the attention "
            "layers rotate nothing")
    if hf.get("hidden_act", "silu") != "silu":
        raise NotImplementedError(f"hidden_act {hf['hidden_act']!r}")
    d = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"], num_hidden_layers=n,
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        head_dim_override=hf.get("head_dim") or 0,
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        layer_types=tuple(hf["layer_types"])[:n],
        linear_num_heads=hf["linear_num_value_heads"],
        linear_num_key_heads=hf["linear_num_key_heads"],
        linear_key_head_dim=hf["linear_key_head_dim"],
        linear_value_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel_dim=hf["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=bool(hf.get("linear_allow_neg_eigval", False)),
        state_slots=int(hf.get("serving", {}).get("max_requests_per_batch", 0)),
    )
    d.update(kw)
    return config(**d)


# ---------------------------------------------------------------------------
# Parameters: three stacked groups (two mixers, the FFN) and the ends


def _group_shapes(cfg: OlmoHybridConfig, group: str) -> Dict[str, Tuple[int, ...]]:
    D = cfg.hidden_size
    if group == "gdn":
        H, dv = cfg.linear_num_heads, cfg.linear_value_head_dim
        return {"w_qkv": (D, cfg.conv_dim),
                "conv_w": (cfg.linear_conv_kernel_dim, cfg.conv_dim),
                "w_gates": (D, 2 * H), "dt_bias": (H,), "A_log": (H,),
                "o_norm_scale": (dv,), "w_ogate": (D, H * dv),
                "wo": (H * dv, D), "mixer_norm_scale": (D,)}
    if group == "attn":
        H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        return {"wq": (D, H * d), "wk": (D, KV * d), "wv": (D, KV * d),
                "q_norm_scale": (H * d,), "k_norm_scale": (KV * d,),
                "wo": (H * d, D), "mixer_norm_scale": (D,)}
    F = cfg.intermediate_size
    return {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D),
            "mlp_norm_scale": (D,)}


GROUPS = ("gdn", "attn", "ffn")
_F32_LEAVES = ("dt_bias", "A_log")


def init_params(key, cfg: OlmoHybridConfig) -> Dict[str, Any]:
    """The family's own draw: 0.02 (0.02 / sqrt(2 N) for ``wo`` and
    ``w_down``), and the Gated DeltaNet layer's published
    initialisation: ``A`` uniform in (0, 16), ``dt`` log-uniform in
    (0.001, 0.1) with ``dt_bias`` its inverse softplus, the taps at
    1 / sqrt(taps) (PyTorch's depthwise init is of that order)."""
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 64))

    def leaf(name, shape):
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

    params = {
        "embed": leaf("embed", (cfg.vocab_size, cfg.hidden_size)),
        "final_norm_scale": leaf("final_norm_scale", (cfg.hidden_size,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = leaf("lm_head", (cfg.hidden_size, cfg.vocab_size))
    for group in GROUPS:
        n = cfg.count(group)
        if n:
            params[group] = {
                name: leaf(name, (n,) + shape)
                for name, shape in _group_shapes(cfg, group).items()}
    return params


def param_pspecs(cfg: OlmoHybridConfig, *, pipeline: bool = False) -> Dict[str, Any]:
    """Every weight whole on every device: one chip, or replicas
    (``validate_serving`` refuses ``model > 1``)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda _: P(), shapes)


def num_params(cfg: OlmoHybridConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def validate_serving(cfg: OlmoHybridConfig, serving, mesh, *, specinfer: bool = False) -> None:
    """The combinations this family's per-slot state cannot serve yet,
    refused at engine construction, each naming what is missing."""
    from ..core.mesh import MODEL_AXIS

    def refuse(what, why):
        raise NotImplementedError(f"olmo_hybrid does not serve {what}: {why}")

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
               "the attention layers' pool has no scale rows in this "
               "family's cache")
    if serving.fused_decode:
        refuse(f"fused_decode={serving.fused_decode!r}",
               "the fused prologue knows one kind of layer, a norm a head "
               "and rope")
    if serving.kv_shard == "context":
        refuse(f"kv_shard={serving.kv_shard!r}",
               "the recurrent state of a row lives on one shard")
    if mesh.shape.get(MODEL_AXIS, 1) > 1:
        refuse("a mesh with model > 1",
               "the recurrent state is not sharded over its heads yet")


def _no_state_rollback(*_a, **_k):
    raise NotImplementedError(
        "olmo_hybrid keeps per-slot recurrent state: committing, copying or "
        "reordering cache lines would need that state rolled back or moved "
        "with them, and no snapshot is kept")


commit_kv_paged = reorder_slots_paged = copy_page_kv = _no_state_rollback
gather_page_kv = scatter_page_kv = _no_state_rollback
init_kv_cache = kv_cache_pspecs = serve_step = _no_state_rollback
commit_kv = reorder_slots = _no_state_rollback


# ---------------------------------------------------------------------------
# Cache: the attention layers' paged pool, the recurrent layers' per-slot state


#: a lane tile (``ops/flash_attention.LANES``; the kernels are imported
#: where they are called, so the constant is written again)
LANES = 128


def lane_pack(H: int, dv: int) -> int:
    """Heads kept side by side on the minor axis of the recurrent
    state: the least divisor p of H that makes ``p dv`` whole lane
    tiles, 1 where dv is or no divisor does (the tests' tiny widths).
    The device tiles a float32 array's two minor axes at (8, 128), so a
    per-slot state whose minor extent is no multiple of 128 is held,
    read and written with its padding."""
    return next((p for p in range(1, H + 1)
                 if H % p == 0 and p * dv % LANES == 0), 1)


def pack_heads(s, p: int):
    """(..., H, dk, dv) -> (..., H / p, dk, p dv): head ``h`` on lanes
    ``(h % p) dv ..`` of row ``h // p``. A transpose: for the tests and
    one prefilling row's state, never a layer's."""
    *lead, H, dk, dv = s.shape
    s = s.reshape(*lead, H // p, p, dk, dv)
    return jnp.moveaxis(s, -3, -2).reshape(*lead, H // p, dk, p * dv)


def unpack_heads(s, p: int):
    """:func:`pack_heads` undone."""
    *lead, Hp, dk, W = s.shape
    s = s.reshape(*lead, Hp, dk, p, W // p)
    return jnp.moveaxis(s, -2, -3).reshape(*lead, Hp * p, dk, W // p)


def init_paged_kv_cache(
    cfg: OlmoHybridConfig, num_pages: int, page_size: int, dtype=None,
    kv_quant: Optional[str] = None, extra_rows: int = 0, *,
    num_slots: Optional[int] = None, cache_len: Optional[int] = None,
):
    """``k``/``v``: (attention layers, num_pages+1, page_size, KV * d),
    a line's heads MERGED on the minor axis (30 heads are no multiple
    of a sublane tile: the device lays a (..., page, 30, 128) array out
    with its heads padded to 32 for the line write and with the page
    inside the heads for the kernel, three pool copies a step), row
    ``num_pages`` the scratch page; ``state``: (recurrent layers,
    slots, H / p, dk, p dv) float32 whatever the cache's dtype, p =
    :func:`lane_pack` heads side by side on the minor axis (published:
    two heads of 192, (.., 15, 96, 384): at (.., 30, 96, 192) the device
    pads every row of 192 to 256 lanes, 1.70 GB for nine layers' 1.27,
    and a step moves the padding too; tests/test_chip_compile.py);
    ``conv``: (recurrent layers, taps - 1, slots, conv_dim), each
    slot's newest convolution inputs, oldest first."""
    if kv_quant is not None or extra_rows:
        raise NotImplementedError(
            "olmo_hybrid's pool is neither quantized nor row-sharded "
            "(validate_serving refuses kv_quant and kv_shard='context')")
    slots = num_slots or cfg.state_slots
    if not slots:
        raise ValueError(
            "olmo_hybrid keeps per-slot state: init_paged_kv_cache needs "
            "num_slots (the engine passes its own)")
    dt = dtype or cfg.dtype
    pool = (cfg.count("attn"), num_pages + 1, page_size,
            cfg.num_key_value_heads * cfg.head_dim)
    n = cfg.count("gdn")
    H, dv = cfg.linear_num_heads, cfg.linear_value_head_dim
    p = lane_pack(H, dv)
    return {
        "k": jnp.zeros(pool, dt), "v": jnp.zeros(pool, dt),
        "state": jnp.zeros((n, slots, H // p, cfg.linear_key_head_dim,
                            p * dv), jnp.float32),
        "conv": jnp.zeros((n, cfg.linear_conv_kernel_dim - 1, slots,
                           cfg.conv_dim), dt),
    }


def paged_kv_cache_pspecs(cfg: OlmoHybridConfig = None, *, pipeline: bool = False,
                          kv_quant: Optional[str] = None,
                          kv_shard: Optional[str] = None):
    return {name: P() for name in ("k", "v") + SLOT_STATE}


# ---------------------------------------------------------------------------
# The gated delta rule


def _chunk(q, k, v, g, b, s):
    """One sub-chunk of the chunk form. q, k (R, Hk, c, dk), v (R, H, c,
    dv), g, b (R, H, c), all float32, a position that is not real at
    g = 0 and b = 0; ``s`` (R, H, dk, dv) the incoming state; key head
    j is that of the value heads j H / Hk on, and ``k k^T`` and
    ``q k^T`` are taken once a key head.
    -> (o (R, H, c, dv), the state after the sub-chunk)."""
    c = q.shape[2]
    group = v.shape[1] // q.shape[1]
    # a key head's array for each of its value heads (one a head: itself)
    per_value = (lambda x: x) if group == 1 else (
        lambda x: jnp.repeat(x, group, axis=1))
    G = jnp.cumsum(g, axis=-1)                               # (R, H, c)
    i = jnp.arange(c)
    upto = i[:, None] >= i[None, :]                          # j <= i
    # exp(G_i - G_j) on the triangle and nowhere else: above it the
    # difference is positive and large
    decay = jnp.where(upto, jnp.exp(jnp.where(
        upto, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    a = b[..., None] * decay * per_value(ein("rhid,rhjd->rhij", k, k))
    a = jnp.where(i[:, None] > i[None, :], a, 0.0)           # strictly below
    into = jnp.exp(G)[..., None]                             # exp(G_i)
    kv, qv = per_value(k), per_value(q)
    rhs = b[..., None] * (v - into * ein("rhid,rhde->rhie", kv, s))
    u = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=a.dtype), rhs, lower=True, unit_diagonal=True)
    o = into * ein("rhid,rhde->rhie", qv, s) + ein(
        "rhij,rhje->rhie",
        decay * per_value(ein("rhid,rhjd->rhij", q, k)), u)
    left = jnp.exp(G[..., -1:] - G)[..., None]               # exp(G_c - G_j)
    s = jnp.exp(G[..., -1])[..., None, None] * s + ein(
        "rhjd,rhje->rhde", kv * left, u)
    return o, s


def _lanes_of(x, p: int, dv: int):
    """(R, H / p, p, ...) -> (R, H / p, ..., p dv): each of a row's p
    heads' values on that head's dv lanes, by p broadcasts and p - 1
    selects on the lane index (p = 1: the broadcast alone). Elementwise,
    so a consumer's fusion makes it where it reads it."""
    out = jnp.broadcast_to(x[:, :, 0, ..., None], x.shape[:2] + x.shape[3:] + (p * dv,))
    lane = jnp.arange(p * dv)
    for j in range(1, p):
        out = jnp.where(lane >= j * dv, x[:, :, j, ..., None], out)
    return out


def gated_delta(q, k, v, g, b, state, count, fresh):
    """The gated delta rule of one step over the carried state.

    q, k (R, C, Hk, dk): L2-normalised, q scaled; v (R, C, H, dv); g
    (R, C, H): the log of each token's decay; b (R, C, H): its write
    strength; ``state`` (R, H, dk, dv) float32 or, as the cache holds
    it, (R, H / p, dk, p dv) with p heads side by side on the lanes
    (:func:`pack_heads`; p is read off the shapes); ``count`` (R,): the
    row's real tokens, its first columns; ``fresh`` (R,): rows that
    start from a zero state. Returns (o (R, C, H, dv) float32, the
    state after each row's last real token, laid out as it came). A row
    with no real token keeps its state bitwise.

    Hk key heads serve H value heads: key head j is the q and k of the
    value heads j H / Hk on, each with a state of its own (Olmo-Hybrid:
    Hk = H; Qwen3-Next: 16 for 32). Both are read off the shapes.

    C == 1 is the recurrence itself ON THE PACKED FORM (no transpose of
    a state), with the state read once for both ``S^T k`` and ``S^T q``
    (``o = a S^T q + (k . q) u`` is ``(a S + k u^T)^T q``); C > 1 the
    chunk form at sub-chunks of :data:`SUB_CHUNK` (module docstring) on
    the heads apart: one prefilling row's state is unpacked and packed
    again around it."""
    R, C, Hk, dk = q.shape
    H, dv = v.shape[2:]
    p = H // state.shape[1]
    f32 = jnp.float32
    real = jnp.arange(C)[None, :] < count[:, None]           # (R, C)
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    g = jnp.where(real[..., None], g.astype(f32), 0.0)
    b = jnp.where(real[..., None], b.astype(f32), 0.0)
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state)
    if C == 1:
        a, kq = jnp.exp(g), jnp.sum(k * q, axis=-1)
        if Hk < H:  # a broadcast, made where the state's fusion reads it
            q, k, kq = (jnp.repeat(x, H // Hk, axis=2) for x in (q, k, kq))
        rows = lambda x: x[:, 0].reshape((R, H // p, p) + x.shape[3:])
        a, b, kq = (_lanes_of(rows(x), p, dv)                # (R, H/p, p dv)
                    for x in (a, b, kq))
        q, k = (_lanes_of(rows(x), p, dv) for x in (q, k))   # (R, H/p, dk, p dv)
        # S^T k and S^T q as products summed over dk, not as 1920
        # matrix-vector products of two rows each: the state streams
        # through once and the matrix unit would load it as weights
        sk = a * jnp.sum(s0 * k, axis=-2)                    # (R, H/p, p dv)
        sq = a * jnp.sum(s0 * q, axis=-2)
        u = b * (v[:, 0].reshape(sk.shape) - sk)
        s1 = a[:, :, None] * s0 + k * u[:, :, None]
        o = (sq + kq * u).reshape(R, 1, H, dv)
    else:
        c = min(C, SUB_CHUNK)
        if C % c:
            raise ValueError(f"a chunk of {C} is no multiple of {c}")
        heads = lambda x: jnp.moveaxis(x, 2, 1)              # (R, H, C, ...)
        q, k, v, g, b = map(heads, (q, k, v, g, b))
        s1, outs = unpack_heads(s0, p), []
        for lo in range(0, C, c):
            o, s1 = _chunk(*(x[:, :, lo:lo + c] for x in (q, k, v, g, b)), s1)
            outs.append(o)
        o = jnp.moveaxis(jnp.concatenate(outs, axis=2), 1, 2)
        s1 = pack_heads(s1, p)
    return o, jnp.where((count > 0)[:, None, None, None], s1, state)


def recurrence_c1(q, k, v, g, b, states, index, count, fresh):
    """:func:`gated_delta` at one column as ONE pass over the state
    (``serve/kernels.gdn_recur_c1``): a token's arrays (R, ...) with no
    column axis (q and k a KEY head), ``states`` the recurrent layers'
    whole stack, of which this layer is ``index``. -> (o (R, H, dv) float32, ``states`` with
    the layer's rows updated in place)."""
    from ..serve.kernels import gdn_recur_c1

    live = (count > 0)[:, None]
    o, states = gdn_recur_c1(
        states, index, q, k, v,
        jnp.exp(jnp.where(live, g.astype(jnp.float32), 0.0)),
        jnp.where(live, b, 0.0), count, fresh)
    return o.reshape(v.shape), states


def step_rows(rule, token, states, index, ctx, kernel=None):
    """A per-row recurrence of a step on its flat token axis.
    ``token``: arrays (N, ...) a token; ``rule(*arrays (R, C, ...),
    state (R, ...), count, fresh) -> (o (R, C, ...) float32, state)``
    is :func:`gated_delta`'s contract (a row's real tokens lead, a row
    with none keeps its state bitwise); ``states`` the recurrent
    layers' stacked states, of which this layer is ``index``. -> (o (N,
    ...) float32, ``states`` with the layer's rows updated in place).

    Rows that hold one token take the rule at C = 1, all of them at
    once; each row that holds more takes it at the step's chunk, in a
    loop over those rows alone (module docstring). ``kernel(*arrays (R,
    ...), states, index, count, fresh) -> (o (R, ...), states)``, where
    a family hands one over (both do: :func:`recurrence_c1` here,
    ``granite_hybrid.recurrence_c1``), is the rule of the C = 1 PROGRAM
    under ``kernels="pallas"``, on the whole stack in place; a mixed
    program's rows of one token keep ``rule`` (a kernel result of
    [slots, 1, ...] ahead of its ragged call would key it as a decode
    step: ``benchmarks/harness/reduce.py::kernel_chunk``). A state
    comes and goes as the family's cache lays it out (Olmo's with its
    heads packed on the lanes): ``rule`` and ``kernel`` read the layout
    off the shapes, nothing here re-lays a layer."""
    count, fresh, place = ctx["q_len"], ctx["fresh"], ctx["place"]
    R, C = place.shape
    N = token[0].shape[0]
    if C == 1 and kernel is not None and ctx["kernels"] == "pallas":
        return kernel(*token, states, index, count, fresh)
    s_l = _layer_of(states, index)
    if C == 1:  # the token axis is the rows
        o, s_l = rule(*(x[:, None] for x in token), s_l, count, fresh)
        return o[:, 0], lax.dynamic_update_index_in_dim(states, s_l, index, 0)
    first = place[:, 0]
    single = count == 1
    o1, s_l = rule(*(x[first][:, None] for x in token), s_l,
                   single.astype(count.dtype), fresh)
    states = lax.dynamic_update_index_in_dim(states, s_l, index, 0)
    o = jnp.zeros((N,) + o1.shape[2:], jnp.float32)
    o = o.at[jnp.where(single, first, N)].set(o1[:, 0], mode="drop")
    (rows,) = jnp.nonzero(count > 1, size=R, fill_value=0)
    cols = jnp.arange(C)
    block = (1, 1) + states.shape[2:]

    def one_row(i, carry):
        o, states = carry
        r = rows[i]
        at = lax.dynamic_index_in_dim(place, r, 0, keepdims=False)   # (C,)
        n = lax.dynamic_slice(count, (r,), (1,))
        where = (index, r) + (0,) * (states.ndim - 2)
        s_r = lax.dynamic_slice(states, where, block)
        o_r, s_r = rule(*(x[at][None] for x in token), s_r[0],
                        n, lax.dynamic_slice(fresh, (r,), (1,)))
        o = o.at[jnp.where(cols < n, at, N)].set(o_r[0], mode="drop")
        return o, lax.dynamic_update_slice(states, s_r[None], where)

    return lax.fori_loop(0, jnp.sum(count > 1), one_row, (o, states))


# ---------------------------------------------------------------------------
# The blocks


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_mixer(ctx, carried, index, h, qkv, p, *, heads, beta_scale):
    """A Gated DeltaNet layer between its input projection and its
    output norm, on the step's flat token axis: ``qkv`` (N, channels)
    = ``[q' | k' | v']`` through the layer's convolution (its state in
    ``carried["conv"]``) and SiLU, q and k L2-normalised a head, the
    gates ``[b | a] = h w_gates`` (``b = beta_scale sigmoid(.)``), the
    rule over ``carried["state"]`` (:func:`step_rows`). ``heads``:
    (key heads, value heads, dk, dv); ``p``: the layer's ``conv_w``,
    ``w_gates``, ``A_log``, ``dt_bias``. -> (o (N, value heads, dv)
    float32, ``carried``). Shared with ``models/qwen3_next.py``."""
    Hk, H, dk, dv = heads
    f32 = jnp.float32
    c, conv = short_conv(
        qkv, p["conv_w"], _layer_of(carried["conv"], index),
        ctx["row"], ctx["col"], ctx["q_len"], ctx["fresh"], ctx["place"])
    carried = dict(carried, conv=lax.dynamic_update_index_in_dim(
        carried["conv"], conv, index, 0))
    q, k, v = jnp.split(jax.nn.silu(c), (Hk * dk, 2 * Hk * dk), axis=-1)
    q = _l2norm(q.reshape(-1, Hk, dk)) * dk ** -0.5
    k = _l2norm(k.reshape(-1, Hk, dk))
    gates = _mm(h, p["w_gates"]).astype(f32)
    b = jax.nn.sigmoid(gates[:, :H]) * beta_scale
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        gates[:, H:] + p["dt_bias"].astype(f32))
    o, state = step_rows(gated_delta, (q, k, v.reshape(-1, H, dv), g, b),
                         carried["state"], index, ctx, kernel=recurrence_c1)
    return o, dict(carried, state=state)


def _gdn_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    B, T, D = x.shape
    H, dv = cfg.linear_num_heads, cfg.linear_value_head_dim
    f32 = jnp.float32
    with sublayer("mixer"):
        h = x.reshape(B * T, D)
        o, carried = delta_mixer(
            ctx, carried, index, h, _mm(h, p["w_qkv"]), p,
            heads=(cfg.key_heads, H, cfg.linear_key_head_dim, dv),
            beta_scale=2.0 if cfg.linear_allow_neg_eigval else 1.0)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
        o = (o * p["o_norm_scale"].astype(f32)).astype(x.dtype)
        o = o * jax.nn.silu(_mm(h, p["w_ogate"])).reshape(-1, H, dv)
        out = _mm(o.reshape(B, T, H * dv), p["wo"])
    return x + _norm(cfg, out, p["mixer_norm_scale"], None), carried


def _write_lines(pool, layer, phys, off, lines):
    """The step's new lines (B, T, W) into layer ``layer`` of a stacked
    merged pool (L, P+1, page, W), in place: ONE scatter of rows into
    the pool's free (L (P+1) page, W) view. (Indexed by (layer, page,
    offset), ``transformer._write_kv_lines``' form, the compiler
    flattens a packed rung's scatter itself and the flattened one
    carries no name: 3.5 ms of a mixed step under no scope.)"""
    L, P1, ps, W = pool.shape
    rows = (layer * P1 + phys) * ps + off
    flat = pool.reshape(L * P1 * ps, W).at[rows.reshape(-1)].set(
        lines.reshape(-1, W).astype(pool.dtype))
    return flat.reshape(pool.shape)


def _attn_block(cfg, ctx, stack, index, x, carried):
    from ..serve import kernels as _pk

    p = layer_weights(stack, index)
    B, T, _ = x.shape
    H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with sublayer("attn.proj"):  # a norm over the whole projection, no rope
        q = _norm(cfg, _mm(x, p["wq"]), p["q_norm_scale"], None).reshape(B, T, H, d)
        k = _norm(cfg, _mm(x, p["wk"]), p["k_norm_scale"], None)
        v = _mm(x, p["wv"])
    with sublayer("attn.write"):
        kp, vp = (_write_lines(pool, index, ctx["phys"], ctx["off"], lines)
                  for pool, lines in ((carried["k"], k), (carried["v"], v)))
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
        out = _mm(o, p["wo"])
    return x + _norm(cfg, out, p["mixer_norm_scale"], None), dict(carried, k=kp, v=vp)


def _ffn_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    return x + _norm(cfg, _ffn(cfg, p, x), p["mlp_norm_scale"], None), carried


# ---------------------------------------------------------------------------
# The step


def step_context(tokens, positions, page_table, page_size, cache_len,
                 kernels, pack):
    """What the blocks of a paged step with per-slot state read beside
    their weights: ``((tokens, positions) on the step's token axis,
    ctx)``. The token axis is (R, C) itself or, with ``pack``, the
    packed one (1, pack); ``ctx``: each token's page and offset, the
    table, the mask and the Pallas kernel's work list
    (serve/kernels.step_work) for the attention calls, each row's real
    tokens ``q_len`` and whether it starts ``fresh`` (its first position
    is 0), and the token axis's geometry (``row`` / ``col`` of each token,
    ``place`` of each (row, column)) for :func:`step_rows` and
    ``lfm2_moe.short_conv``."""
    from ..serve.kernels import paged_serve_mask, real_query_lengths, step_work

    R, C = tokens.shape
    q_len = real_query_lengths(positions, cache_len)  # real columns lead
    if pack is None:
        token_axis = (tokens, positions)
        phys, off = _page_lookup(page_table, positions, page_size)
        place = jnp.arange(R * C, dtype=jnp.int32).reshape(R, C)
        row = jnp.repeat(jnp.arange(R, dtype=jnp.int32), C)
        col = jnp.tile(jnp.arange(C, dtype=jnp.int32), R)
        pack_idx = None
    else:
        (*token_axis, phys, off), pack_idx = _pack_tokens(
            tokens, positions, q_len, page_table, page_size, cache_len, pack)
        place, flat = pack_idx
        row, col = flat // C, flat % C
    return token_axis, dict(
        phys=phys, off=off, page_table=page_table, kernels=kernels,
        q_len=q_len, pack=pack_idx,
        mask=paged_serve_mask(None, positions, page_table.shape[1],
                              page_size, cache_len),
        work=(step_work(positions, q_len, page_size, page_table.shape[1])
              if kernels == "pallas" else None),
        row=row, col=col, place=place,
        fresh=(q_len > 0) & (positions[:, 0] == 0),
    )


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
    cfg: OlmoHybridConfig,
    cache_len: int,
    all_logits: bool = False,
    kernels: str = "xla",
    pack: Optional[int] = None,
    **unsupported,
):
    """The engine's paged step (models/transformer.serve_step_paged's
    contract, its packed token axis included) over the layer order. A
    row's real positions are its first columns, consecutive; a row
    whose first position is 0 starts from zero states (module
    docstring)."""
    if mask is not None or cache_positions is not None or any(
            v for v in unsupported.values()):
        _no_state_rollback()
    if pack is not None and all_logits:
        raise ValueError("a packed token axis returns one logits row a row")
    token_axis, ctx = step_context(
        tokens, positions, page_table, cache["k"].shape[2], cache_len,
        kernels, pack)
    x = _embed_in(cfg, params, *token_axis)
    blocks = {
        name: functools.partial(fn, cfg, ctx)
        for name, fn in (("gdn", _gdn_block), ("attn", _attn_block),
                         ("ffn", _ffn_block))}
    x, new_cache = run_layers(cfg.kinds, blocks, params, x, cache)
    return _head_logits(cfg, params, x, logits_idx, ctx["pack"],
                        all_logits), new_cache

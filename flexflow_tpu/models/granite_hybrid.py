"""Granite 4.0-H model family (ibm-granite, ``model_type:
granitemoehybrid`` with no routed expert): a dense decoder whose layers
have one of TWO mixers, in the order ``layer_types`` gives (nine
``mamba`` to one ``attention`` as published):

* ``mamba``: a Mamba-2 selective state-space layer. ``[z | xBC | dt'] =
  u W_in``; each channel of ``xBC`` through a depthwise causal
  convolution of ``mamba_d_conv`` taps with a bias, then SiLU, then
  split ``[xs | B | C]``. A head keeps a (P, N) float32 state a
  request: ``S <- a S + (dt xs) B^T``, ``y = S C + D xs``, with ``dt =
  softplus(dt' + dt_bias)`` and the decay ``a = exp(-exp(A_log) dt)``
  computed from the token, B and C SHARED by the heads (one group). The
  output is ``rmsnorm(y * silu(z)) W_o``: the gate goes in before the
  norm, which runs over the whole inner width. A decoding request
  carries the state and the last taps - 1 inputs of the convolution:
  per-slot state, no K/V.
* ``attention``: softmax attention over the paged K/V pool at the scale
  ``attention_multiplier``, with no rope, no bias and no q/k norm.
* both kinds: ``x += residual_multiplier * mixer(rmsnorm(x))``, then
  ``x += residual_multiplier * ffn(rmsnorm(x))``, a SiLU-gated FFN; the
  embedding times ``embedding_multiplier``, the tied head's logits over
  ``logits_scaling``.

The equations are written out in
``benchmarks/references/granite_hybrid.py`` (the recurrence token by
token), which the tests hold this file to, and which they hold to the
published modelling code.

Serving only, on the paged path (``kv_layout="paged"``), through the
engine's ordinary step programs, as ``models/olmo_hybrid.py``:

* the layer loop is :func:`transformer.run_layers`; the K/V pool of the
  attention layers and the state-space layers' states are its carry,
  updated in place (tests/test_chip_compile.py: at the published widths
  and 64 slots the state is 4.83 GB beside 6.38 GB of weights, and a
  copy of it does not fit the chip).
* the cache is the paged K/V pool of the ATTENTION layers only
  (``k``/``v``: (attention layers, pages+1, page, KV * d), a line's
  heads merged on the minor axis as ``models/lfm2_moe.py``'s) plus
  per-SLOT state (``SLOT_STATE``): ``state`` (mamba layers, slots, H,
  P, N) float32 and ``conv`` (mamba layers, taps - 1, slots, H P +
  2 N): the convolution's newest inputs, in the cache's dtype.
* what a step is handed decides everything: a row whose chunk starts at
  position 0 starts from zero states, the scratch position and padded
  rows update nothing (a row with no real token keeps its states
  bitwise), a chunk leaves the states of its last real token.
* the step takes the engine's PACKED token axis (``PACKED_STEP``). The
  scan runs a ROW at a time: :func:`selective_scan` is the recurrence
  itself for one token a row (the C = 1 step, and the rows of a mixed
  step that hold one token, all of them at once) and the chunk (SSD)
  form for more (each row of a mixed step that prefills, in a loop over
  those rows alone: a mixed step holds one or two of them beside 60
  that decode, and the form for all rows at once would build a (H, C,
  C) decay mask for every padded row). Under ``kernels="pallas"`` the
  C = 1 PROGRAM's recurrence is :func:`recurrence_c1`, one pass over
  the state in a Pallas kernel (XLA makes two reads and a write of
  it); the mixed programs' rows of one token and ``kernels="xla"``
  keep :func:`selective_scan`. Every exponent of the chunk form
  is a difference ``G_i - G_j`` with j <= i taken on the masked
  triangle (``exp(-G)`` overflows float32 inside one chunk at a decay
  of 0.5 a token).

What it refuses, at construction (``validate_serving``), each because
the per-slot state has no such operation yet: prefix caching, SpecInfer
and beam search, ``kv_quant``, ``fused_decode``, ``kv_shard="context"``,
the dense layout, a mesh with ``model > 1``. ``from_hf`` refuses the
family's larger siblings by name: routed experts (``num_local_experts >
0``), rope (``position_embedding_type != "nope"``), more than one group
of B and C.

Weight names are chosen knowing ``benchmarks/harness/model.py::
make_params``' rule (a leaf whose name holds ``bias`` or starts with
``b`` is drawn zero, ``norm_scale`` one, ``wo`` and ``w_down`` at 0.02 /
sqrt(2 N)): the three projections that write into the residual stream
are ``w_out``, NOT ``wo`` / ``w_down``, so that they are drawn at the
plain 0.02 as the published initialisation draws every linear layer:
this family's depth scaling is its ``residual_multiplier``, and under
both the layers would be a fortieth of the stream and a comparison of
logits blind to them. ``w_in`` holds z, xBC and dt' side by side,
``conv_w`` (taps, channels) with the last tap on the token itself,
``conv_bias``; ``dt_bias``, ``A_log`` and ``D`` are float32 a head;
``ssm_norm_scale`` the (H P,) scale of the gated norm;
``mixer_norm_scale`` / ``mlp_norm_scale`` the norms before the two
sublayers. The FFN's gate and up projections are two matrices
(published: the halves of ``input_linear``).
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
from .olmo_hybrid import _write_lines, step_context, step_rows
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
    layer_weights,
    run_layers,
    seeded_normal,
)

MAMBA, ATTENTION = "mamba", "attention"
# the cache entries that are per SLOT, not per page
SLOT_STATE = ("state", "conv")
# the one of them a real token updates by a recurrence
# (SchedulerStats.recurrent_updates)
RECURRENT_STATE = "state"
FUSED_DECODE = ()
PACKED_STEP = True
HIGHEST = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig(DecoderConfig):
    layer_types: Tuple[str, ...] = ()
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    # slots of per-slot state where ``init_paged_kv_cache`` is not told
    # (``benchmarks/tools/fit.py``; the engine always tells)
    state_slots: int = 0

    def __post_init__(self):
        super().__post_init__()
        kinds = self.layer_types
        if len(kinds) != self.num_hidden_layers or set(kinds) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {MAMBA!r} or {ATTENTION!r}: got {kinds}")

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """A layer's kind: (mixer group, FFN group)."""
        return tuple(("ssm" if t == MAMBA else "attn", "ffn")
                     for t in self.layer_types)

    def count(self, group: str) -> int:
        return sum(group in kind for kind in self.kinds)

    @property
    def inner_size(self) -> int:
        """Width of a mamba layer's inner stream: heads x head size (the
        published ``mamba_expand`` x hidden_size)."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels of a mamba layer's convolution: xs, B and C."""
        return self.inner_size + 2 * self.mamba_d_state


def config(**kw) -> GraniteHybridConfig:
    d: Dict[str, Any] = dict(
        vocab_size=100352, hidden_size=2048, intermediate_size=8192,
        num_hidden_layers=40, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=131072, norm_type="rmsnorm", norm_bias=False,
        norm_eps=1e-5, positions="none", activation="silu", glu=True,
        tie_word_embeddings=True,
    )
    d.update(kw)
    period = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    n = d["num_hidden_layers"]
    d.setdefault("layer_types", (period * -(-n // 10))[:n])
    return GraniteHybridConfig(**d)


def tiny(**kw) -> GraniteHybridConfig:
    """CPU test size: both transitions between the two mixers (runs of
    two, one, one mamba layers), three heads of P = 8 over a state of
    N = 16, GQA 4/2."""
    d = dict(
        vocab_size=256, hidden_size=48, intermediate_size=96,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        mamba_n_heads=3, mamba_d_head=8, mamba_d_state=16,
        max_position_embeddings=512,
        layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA, ATTENTION),
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> GraniteHybridConfig:
    """From the published ``config.json`` keys, as they are spelled.
    ``num_hidden_layers`` under ``len(layer_types)`` takes the first
    entries. ``head_dim`` is read where a configuration states it. The
    inner width is ``mamba_n_heads * mamba_d_head`` (the published
    ``mamba_expand * hidden_size`` says the same)."""
    n = kw.get("num_hidden_layers", hf["num_hidden_layers"])
    if hf.get("num_local_experts", 0) > 0:
        raise NotImplementedError(
            f"num_local_experts={hf['num_local_experts']}: this family "
            "serves the dense model, whose shared MLP is the layer's only FFN")
    if hf.get("position_embedding_type", "nope") != "nope":
        raise NotImplementedError(
            f"position_embedding_type={hf['position_embedding_type']!r}: the "
            "attention layers rotate nothing")
    if hf.get("mamba_n_groups", 1) != 1:
        raise NotImplementedError(
            f"mamba_n_groups={hf['mamba_n_groups']}: B and C are shared by "
            "all heads")
    for key in ("attention_bias", "mamba_proj_bias"):
        if hf.get(key):
            raise NotImplementedError(f"{key}: the published model has none")
    if not hf.get("mamba_conv_bias", True):
        raise NotImplementedError("mamba_conv_bias=False: the published "
                                  "convolution has a bias")
    if hf.get("hidden_act", "silu") != "silu":
        raise NotImplementedError(f"hidden_act {hf['hidden_act']!r}")
    d = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["shared_intermediate_size"], num_hidden_layers=n,
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        head_dim_override=hf.get("head_dim") or 0,
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", True)),
        layer_types=tuple(hf["layer_types"])[:n],
        mamba_n_heads=hf["mamba_n_heads"], mamba_d_head=hf["mamba_d_head"],
        mamba_d_state=hf["mamba_d_state"], mamba_d_conv=hf["mamba_d_conv"],
        embedding_multiplier=float(hf["embedding_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        attention_multiplier=float(hf["attention_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]),
        state_slots=int(hf.get("serving", {}).get("max_requests_per_batch", 0)),
    )
    d.update(kw)
    return config(**d)


# ---------------------------------------------------------------------------
# Parameters: three stacked groups (two mixers, the FFN) and the ends


def _group_shapes(cfg: GraniteHybridConfig, group: str) -> Dict[str, Tuple[int, ...]]:
    D = cfg.hidden_size
    if group == "ssm":
        H, inner = cfg.mamba_n_heads, cfg.inner_size
        return {"mixer_norm_scale": (D,),
                "w_in": (D, inner + cfg.conv_dim + H),
                "conv_w": (cfg.mamba_d_conv, cfg.conv_dim),
                "conv_bias": (cfg.conv_dim,),
                "dt_bias": (H,), "A_log": (H,), "D": (H,),
                "ssm_norm_scale": (inner,), "w_out": (inner, D)}
    if group == "attn":
        H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        return {"mixer_norm_scale": (D,), "wq": (D, H * d), "wk": (D, KV * d),
                "wv": (D, KV * d), "w_out": (H * d, D)}
    F = cfg.intermediate_size
    return {"mlp_norm_scale": (D,), "w_gate": (D, F), "w_up": (D, F),
            "w_out": (F, D)}


GROUPS = ("ssm", "attn", "ffn")


def init_params(key, cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """The family's own draw: every projection at 0.02 (the published
    ``initializer_range``; the residual multiplier is the depth
    scaling), and the Mamba-2 layer's initialisation: ``A`` = 1..H,
    ``dt`` log-uniform in (0.001, 0.1) with ``dt_bias`` its inverse
    softplus, ``D`` = 1, the taps at 1 / sqrt(taps) and their bias at a
    fifth (PyTorch's depthwise init is of that order)."""
    std = 0.02
    keys = iter(jax.random.split(key, 64))

    def leaf(name, shape):
        if "norm_scale" in name:
            return jnp.ones(shape, cfg.dtype)
        if name == "A_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[-1] + 1, dtype=jnp.float32)), shape)
        if name == "D":
            return jnp.ones(shape, jnp.float32)
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                next(keys), shape, jnp.float32, math.log(1e-3), math.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        scale = {"conv_bias": 0.2,
                 "conv_w": 1.0 / math.sqrt(cfg.mamba_d_conv)}.get(name, std)
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


def param_pspecs(cfg: GraniteHybridConfig, *, pipeline: bool = False) -> Dict[str, Any]:
    """Every weight whole on every device: one chip, or replicas
    (``validate_serving`` refuses ``model > 1``)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda _: P(), shapes)


def num_params(cfg: GraniteHybridConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def validate_serving(cfg: GraniteHybridConfig, serving, mesh, *, specinfer: bool = False) -> None:
    """The combinations this family's per-slot state cannot serve yet,
    refused at engine construction, each naming what is missing."""
    from ..core.mesh import MODEL_AXIS

    def refuse(what, why):
        raise NotImplementedError(f"granite_hybrid does not serve {what}: {why}")

    if serving.kv_layout != "paged":
        refuse(f"kv_layout={serving.kv_layout!r}",
               "only the paged step carries the state-space layers' states "
               "beside the pool")
    if serving.prefix_caching:
        refuse("prefix_caching=True",
               "pages can be shared between requests, a state-space layer's "
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
        "granite_hybrid keeps per-slot recurrent state: committing, copying "
        "or reordering cache lines would need that state rolled back or "
        "moved with them, and no snapshot is kept")


commit_kv_paged = reorder_slots_paged = copy_page_kv = _no_state_rollback
gather_page_kv = scatter_page_kv = _no_state_rollback
init_kv_cache = kv_cache_pspecs = serve_step = _no_state_rollback
commit_kv = reorder_slots = _no_state_rollback


# ---------------------------------------------------------------------------
# Cache: the attention layers' paged pool, the mamba layers' per-slot state


def init_paged_kv_cache(
    cfg: GraniteHybridConfig, num_pages: int, page_size: int, dtype=None,
    kv_quant: Optional[str] = None, extra_rows: int = 0, *,
    num_slots: Optional[int] = None, cache_len: Optional[int] = None,
):
    """``k``/``v``: (attention layers, num_pages+1, page_size, KV * d),
    a line's heads MERGED on the minor axis (at head size 64 the device
    lays a (..., page, KV, 64) array out with the page on its lanes and
    a step re-lays the whole pool: ``models/lfm2_moe.py``), row
    ``num_pages`` the scratch page; ``state``: (mamba layers, slots, H,
    P, N) float32 whatever the cache's dtype; ``conv``: (mamba layers,
    taps - 1, slots, conv_dim), each slot's newest convolution inputs,
    oldest first."""
    if kv_quant is not None or extra_rows:
        raise NotImplementedError(
            "granite_hybrid's pool is neither quantized nor row-sharded "
            "(validate_serving refuses kv_quant and kv_shard='context')")
    slots = num_slots or cfg.state_slots
    if not slots:
        raise ValueError(
            "granite_hybrid keeps per-slot state: init_paged_kv_cache needs "
            "num_slots (the engine passes its own)")
    dt = dtype or cfg.dtype
    pool = (cfg.count("attn"), num_pages + 1, page_size,
            cfg.num_key_value_heads * cfg.head_dim)
    n = cfg.count("ssm")
    return {
        "k": jnp.zeros(pool, dt), "v": jnp.zeros(pool, dt),
        "state": jnp.zeros((n, slots, cfg.mamba_n_heads, cfg.mamba_d_head,
                            cfg.mamba_d_state), jnp.float32),
        "conv": jnp.zeros((n, cfg.mamba_d_conv - 1, slots, cfg.conv_dim), dt),
    }


def paged_kv_cache_pspecs(cfg: GraniteHybridConfig = None, *, pipeline: bool = False,
                          kv_quant: Optional[str] = None,
                          kv_shard: Optional[str] = None):
    return {name: P() for name in ("k", "v") + SLOT_STATE}


# ---------------------------------------------------------------------------
# The selective scan


def selective_scan(xs, B, C, dt, state, count, fresh, *, A, D):
    """The state-space update of one step over the carried state.

    xs (R, C, H, P): a token's channels a head; B, C (R, C, N): its
    write and read vectors, shared by the heads; dt (R, C, H): its
    step a head, positive; A, D (H,): ``exp(A_log)`` and the skip;
    ``state`` (R, H, P, N) float32; ``count`` (R,): the row's real
    tokens, its first columns; ``fresh`` (R,): rows that start from a
    zero state. Returns (y (R, C, H, P) float32, the state after each
    row's last real token). A position that is not real takes ``dt =
    0``: it neither decays nor writes. A row with no real token keeps
    its state bitwise.

    C == 1 is the recurrence itself (the state written, then read);
    C > 1 the chunk form, with ``g = -A dt`` and ``G`` its running sum:
    ``y_i = exp(G_i) S0 C_i + sum_{j<=i} exp(G_i - G_j) (C_i . B_j)
    dt_j xs_j + D xs_i`` and ``S_end = exp(G_end) S0 + sum_j exp(G_end
    - G_j) dt_j xs_j B_j^T``: ``C B^T`` is one product a row, the decay
    mask a head's (module docstring)."""
    n_col = xs.shape[1]
    f32 = jnp.float32
    real = jnp.arange(n_col)[None, :] < count[:, None]       # (R, C)
    xs, B, C = xs.astype(f32), B.astype(f32), C.astype(f32)
    dt = jnp.where(real[..., None], dt.astype(f32), 0.0)
    g = -A.astype(f32) * dt                                  # (R, C, H)
    dx = dt[..., None] * xs                                  # (R, C, H, P)
    skip = D.astype(f32)[:, None] * xs
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state)
    if n_col == 1:
        a = jnp.exp(g[:, 0])[..., None, None]                # (R, H, 1, 1)
        s1 = a * s0 + dx[:, 0, :, :, None] * B[:, 0, None, None, :]
        y = jnp.sum(s1 * C[:, 0, None, None, :], axis=-1)[:, None] + skip
    else:
        ein = functools.partial(jnp.einsum, precision=HIGHEST)
        G = jnp.cumsum(g, axis=1)                            # (R, C, H)
        Gh = jnp.moveaxis(G, 2, 1)                           # (R, H, C)
        i = jnp.arange(n_col)
        upto = i[:, None] >= i[None, :]                      # j <= i
        # exp(G_i - G_j) on the triangle and nowhere else: above it the
        # difference is positive and large
        decay = jnp.where(upto, jnp.exp(jnp.where(
            upto, Gh[..., :, None] - Gh[..., None, :], 0.0)), 0.0)
        mix = decay * ein("rin,rjn->rij", C, B)[:, None]     # (R, H, C, C)
        y = (ein("rhij,rjhp->rihp", mix, dx)
             + jnp.exp(G)[..., None] * ein("rin,rhpn->rihp", C, s0) + skip)
        left = jnp.exp(G[:, -1:] - G)[..., None]             # exp(G_end - G_j)
        s1 = (jnp.exp(G[:, -1])[..., None, None] * s0
              + ein("rjhp,rjn->rhpn", dx * left, B))
    return y, jnp.where((count > 0)[:, None, None, None], s1, state)


def recurrence_c1(xs, B, C, dt, states, index, count, fresh, *, A, D):
    """:func:`selective_scan` at one column as ONE pass over the state
    (``serve/kernels.ssm_recur_c1``): a token's arrays (R, ...) with no
    column axis, ``states`` the mamba layers' whole stack, of which
    this layer is ``index``. -> (y (R, H, P) float32, ``states`` with
    the layer's rows updated in place). What is a token's and small
    (the decay, ``dt xs``, the skip) stays XLA's; the kernel has what
    touches the state."""
    from ..serve.kernels import ssm_recur_c1

    f32 = jnp.float32
    xs = xs.astype(f32)
    dt = jnp.where((count > 0)[:, None], dt.astype(f32), 0.0)
    y, states = ssm_recur_c1(
        states, index, jnp.exp(-A.astype(f32) * dt), dt[..., None] * xs,
        B, C, count, fresh)
    return y.reshape(xs.shape) + D.astype(f32)[:, None] * xs, states


# ---------------------------------------------------------------------------
# The blocks


def _add(cfg, x, out):
    """``x + residual_multiplier * out``, the product rounded once to
    the stream's dtype (0.22 is no bfloat16 number: a bfloat16 scalar
    would be 0.12% off on every sublayer)."""
    scaled = out.astype(jnp.float32) * cfg.residual_multiplier
    return x + scaled.astype(x.dtype)


def _ssm_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    B, T, D = x.shape
    H, hp, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    inner = cfg.inner_size
    f32 = jnp.float32
    with sublayer("mixer"):
        u = _norm(cfg, x, p["mixer_norm_scale"], None).reshape(B * T, D)
        z, xbc, dt = jnp.split(_mm(u, p["w_in"]), (inner, inner + cfg.conv_dim),
                               axis=-1)
        conved, conv = short_conv(
            xbc, p["conv_w"], _layer_of(carried["conv"], index),
            ctx["row"], ctx["col"], ctx["q_len"], ctx["fresh"], ctx["place"])
        carried = dict(carried, conv=lax.dynamic_update_index_in_dim(
            carried["conv"], conv, index, 0))
        xs, b, c = jnp.split(jax.nn.silu(conved + p["conv_bias"].astype(f32)),
                             (inner, inner + N), axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
        # rows of one token take the recurrence at once, each row that
        # prefills the chunk form, in a loop over those rows alone
        consts = dict(A=jnp.exp(p["A_log"].astype(f32)), D=p["D"])
        y, state = step_rows(
            functools.partial(selective_scan, **consts),
            (xs.reshape(-1, H, hp), b, c, dt), carried["state"], index, ctx,
            kernel=functools.partial(recurrence_c1, **consts))
        carried = dict(carried, state=state)
        # the gate goes in before the norm; the norm is over the whole
        # inner width (one group)
        y = y.reshape(-1, inner) * jax.nn.silu(z.astype(f32))
        y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
        y = y.astype(x.dtype) * p["ssm_norm_scale"]
        x = _add(cfg, x, _mm(y.reshape(B, T, inner), p["w_out"]))
    return x, carried


def _attn_block(cfg, ctx, stack, index, x, carried):
    from ..serve import kernels as _pk

    p = layer_weights(stack, index)
    B, T, _ = x.shape
    H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    scale = cfg.attention_multiplier
    with sublayer("attn.proj"):  # no bias, no rope, no q/k norm
        u = _norm(cfg, x, p["mixer_norm_scale"], None)
        q = _mm(u, p["wq"]).reshape(B, T, H, d)
        k, v = _mm(u, p["wk"]), _mm(u, p["wv"])
    with sublayer("attn.write"):
        kp, vp = (_write_lines(pool, index, ctx["phys"], ctx["off"], lines)
                  for pool, lines in ((carried["k"], k), (carried["v"], v)))
    with sublayer("attn.core"):
        q = _spread_queries(q, ctx["pack"])                   # (R, C, H, d)
        if ctx["kernels"] == "pallas":
            k_rows, v_rows, kw = _pallas_pools(kp, vp, None, None, index)
            o = _pk.ragged_paged_attention(
                q, k_rows, v_rows, ctx["page_table"], ctx["mask"], scale=scale,
                row_offset=kw["row_offset"], q_len=ctx["q_len"],
                work=ctx["work"])
        else:
            k_virt, v_virt = (
                _pk.gather_pages(_layer_of(pool, index), ctx["page_table"])
                for pool in (kp, vp))
            split = k_virt.shape[:2] + (KV, d)
            o = _serve_attend(cfg, q, k_virt.reshape(split),
                              v_virt.reshape(split), None, ctx["mask"],
                              scale=scale)
        o = _gather_attended(o, ctx["pack"])
    with sublayer("attn.proj"):
        x = _add(cfg, x, _mm(o, p["w_out"]))
    return x, dict(carried, k=kp, v=vp)


@sublayer("ffn")
def _ffn_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    p = dict(p, w_down=p["w_out"])  # transformer._ffn's name for it
    return _add(cfg, x, _ffn(cfg, p, _norm(cfg, x, p["mlp_norm_scale"], None))), carried


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
    cfg: GraniteHybridConfig,
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
    x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    blocks = {
        name: functools.partial(fn, cfg, ctx)
        for name, fn in (("ssm", _ssm_block), ("attn", _attn_block),
                         ("ffn", _ffn_block))}
    x, new_cache = run_layers(cfg.kinds, blocks, params, x, cache)
    logits = _head_logits(cfg, params, x, logits_idx, ctx["pack"], all_logits)
    with sublayer("head"):
        logits = logits / cfg.logits_scaling
    return logits, new_cache

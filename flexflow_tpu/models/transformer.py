"""The decoder: one configurable decoder-only transformer that every
family of the zoo trains on, serves on and is tested on.

The reference builds each serving architecture as a separate C++ graph
builder (reference ``inference/models/{llama,opt,falcon,mpt,starcoder}.cc``
and Python twins ``python/flexflow/serve/models/*.py``), each wiring the
same operator set with per-family choices (norm type, positional scheme,
MQA/GQA widths, FFN activation, parallel vs sequential block). The
TPU-native design factors that variation into one configurable decoder:
a single `lax.scan`-over-stacked-layers program whose config selects

  * normalisation: LayerNorm (± bias) or RMSNorm,
  * positions: RoPE, learned absolute embeddings, or ALiBi bias,
  * attention widths: MHA / GQA / MQA via ``num_key_value_heads``,
  * FFN: relu/gelu/gelu_tanh/silu, optionally gated (GLU),
  * block topology: sequential (x + attn; x + ffn) or parallel
    (x + attn + ffn, Falcon-style, with one or two input norms),
  * biases and tied embeddings.

Each family module (llama.py, opt.py, falcon.py, mpt.py, ...) is a
config mapping + HF weight converter that re-exports this module's
protocol. Three parts: parameters; the full-sequence pass and the train
step built on it (:func:`forward`, :func:`make_train_step`); the
serving protocol the engine calls (:func:`serve_step`,
:func:`serve_step_paged` and the cache helpers).

Design choices, made for the TPU:
  * **Stacked layers**: all N layers' weights live in one pytree with a
    leading layer dim. One compiled block serves every layer (fast
    compile), the layer dim shards over the ``pipe`` axis for pipeline
    parallelism, and ``jax.checkpoint`` remats per block.
  * **bf16 compute / f32 accumulate** on the MXU via
    ``preferred_element_type``.
  * **Megatron sharding**: QKV/up column-parallel and O/down
    row-parallel on the ``model`` mesh axis, layer stack sharded on
    ``pipe``, KV cache slots on ``data``.
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

from ..core.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
)
from ..obs.sublayers import sublayer


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    num_key_value_heads: int = 12        # 1 = MQA (Falcon-7B, Starcoder)
    max_position_embeddings: int = 2048
    norm_type: str = "layernorm"         # "layernorm" | "rmsnorm"
    norm_bias: bool = True
    norm_eps: float = 1e-5
    positions: str = "rope"              # "rope" | "learned" | "alibi"
    learned_pos_offset: int = 0          # OPT stores positions at idx+2
    rope_theta: float = 10000.0
    activation: str = "gelu"             # "relu"|"gelu"|"gelu_tanh"|"silu"
    glu: bool = False                    # gated FFN (SwiGLU-style)
    parallel_block: bool = False         # Falcon: x + attn(h) + mlp(h)
    parallel_two_norms: bool = False     # Falcon-40B: ln_attn + ln_mlp
    qkv_bias: bool = False
    out_bias: bool = False
    mlp_bias: bool = False
    tie_word_embeddings: bool = True
    # Mixture-of-experts FFN (Mixtral-style, HF MixtralSparseMoeBlock):
    # 0 = dense FFN; E > 0 replaces the FFN with E experts and a linear
    # router taking the top-k per token (softmax over the selected k).
    num_local_experts: int = 0
    num_experts_per_tok: int = 2
    # Qwen2-MoE extensions (HF Qwen2MoeSparseMoeBlock): experts may use
    # their own FFN width; an always-on shared expert (its own glu FFN)
    # joins the routed sum scaled by sigmoid(h @ shared_expert_gate);
    # and norm_topk=False keeps the softmax-over-ALL-experts weights of
    # the selected k WITHOUT renormalizing (qwen2_moe's default),
    # versus the Mixtral renormalize-over-selected behavior.
    moe_intermediate_size: int = 0          # 0 = intermediate_size
    moe_shared_expert_intermediate_size: int = 0  # 0 = no shared expert
    moe_norm_topk: bool = True
    # Sliding-window attention (Mistral-style): w > 0 lets a query at
    # position q attend only keys in (q-w, q]. 0 = full causal. The
    # generic decoder's serving cache keeps its full-length layout: it
    # MASKS the lines beyond the window and does not free them, one
    # window for every layer. A family whose layers differ declares a
    # class of page with a window (``page_classes``,
    # models/smallthinker.py; serve/paging.PageClasses) and has the
    # pages behind it freed.
    sliding_window: int = 0
    # Gemma-style knobs: a head_dim decoupled from hidden/heads (0 =
    # derived — kept as an OVERRIDE field, not resolved at construction,
    # so dataclasses.replace(cfg, num_attention_heads=...) re-derives
    # instead of carrying a stale value), RMSNorm scaling by (1 + w)
    # instead of w, and sqrt(D) input-embedding scaling.
    head_dim_override: int = 0
    norm_plus_one: bool = False
    embed_scale: bool = False
    # Phi-style knobs: partial rotary embeddings (only the first
    # rotary_pct of each head rotates) and an LM-head bias.
    rotary_pct: float = 1.0
    lm_head_bias: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_local_experts and self.mlp_bias:
            # the MoE FFN has no bias path — allocating dead b_up/b_down
            # params would silently diverge from the configured arch
            raise ValueError(
                "mlp_bias is not supported with num_local_experts > 0"
            )
        if self.lm_head_bias and self.tie_word_embeddings:
            # a tied head has no separate lm_head tensor to bias — the
            # configured bias would silently vanish
            raise ValueError(
                "lm_head_bias requires tie_word_embeddings=False"
            )
        rot = int(self.head_dim * self.rotary_pct)
        if self.positions == "rope" and rot % 2:
            # an odd rotary width would silently rotate one dim fewer
            # than HF's partial-rope implementations
            raise ValueError(
                f"rotary_pct={self.rotary_pct} gives an odd rotary "
                f"width {rot} over head_dim={self.head_dim}; pick a "
                "fraction with an even rotated width"
            )

    @property
    def head_dim(self) -> int:
        return (
            self.head_dim_override
            or self.hidden_size // self.num_attention_heads
        )


def _activation(cfg: DecoderConfig, x):
    if cfg.activation == "relu":
        return jax.nn.relu(x)
    if cfg.activation == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if cfg.activation == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if cfg.activation == "silu":
        return jax.nn.silu(x)
    raise ValueError(cfg.activation)


def _norm(cfg: DecoderConfig, x, scale, bias):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        r = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.norm_eps)
        if cfg.norm_plus_one:  # Gemma: weight is an offset from 1
            scale = 1.0 + scale.astype(jnp.float32)
            return ((xf * r) * scale).astype(x.dtype)
        return ((xf * r).astype(x.dtype)) * scale
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = ((xf - mu) * lax.rsqrt(var + cfg.norm_eps)).astype(x.dtype) * scale
    if bias is not None:
        y = y + bias
    return y


def _dense_w(w, dtype):
    """Resolve a possibly-quantized ({"q","scale"}) weight to dense."""
    if isinstance(w, dict):  # int8/int4 weight-only quantization
        from ..quantization import dequantize

        return dequantize(w, dtype)
    return w


def _mm(x, w):
    w = _dense_w(w, x.dtype)
    return jnp.matmul(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# Positions


def rope_freqs(cfg: DecoderConfig, positions: jnp.ndarray):
    # partial rotary (Phi-style): only the first rotary_pct of each
    # head rotates; cos/sin carry that width and apply_rope passes the
    # rest of the head through untouched
    rot = int(cfg.head_dim * cfg.rotary_pct)
    half = rot // 2
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def yarn_inv_freq(d: int, theta: float, factor: float = 1.0,
                  original_max: int = 0, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """The ``d / 2`` rope frequencies of ``d`` rotated channels under
    YaRN: below the channel where ``original_max`` positions hold
    ``beta_fast`` turns the plain ones (theta^(-2i/d)), above the one
    where they hold ``beta_slow`` the plain ones over ``factor``, a
    linear ramp between; ``factor <= 1``: the plain ones. (numpy
    float64, cast by the caller. models/deepseek_v3.py's rope channels
    and models/laguna.py's full layers.)"""
    import numpy as np

    plain = theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)
    if factor <= 1:
        return plain

    def channel(turns):
        return d * math.log(original_max / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(channel(beta_fast)), 0)
    high = min(math.ceil(channel(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


def apply_rope(x, cos, sin):
    rot = cos.shape[-1]
    xr, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    x1, x2 = xr[..., :half], xr[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    out = xr * cos[..., None, :] + rotated * sin[..., None, :]
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass.astype(out.dtype)], axis=-1)
    return out.astype(x.dtype)


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """Standard ALiBi head slopes (power-of-two geometric sequence, with
    the interpolation rule for non-power-of-two head counts)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        s = pow2_slopes(num_heads)
    else:
        n = 2 ** math.floor(math.log2(num_heads))
        s = pow2_slopes(n)
        extra = pow2_slopes(2 * n)[0::2][: num_heads - n]
        s = s + extra
    return jnp.asarray(s, jnp.float32)


# ---------------------------------------------------------------------------
# Parameters

@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _seeded_normal(key, scale, floor, *, shape, dtype):
    draw = jnp.maximum(jax.random.normal(key, shape, jnp.float32), floor)
    return (draw * scale).astype(dtype)


def seeded_normal(key, scale, *, shape, dtype):
    """``normal(key) * scale`` cast to ``dtype`` as ONE program: the
    float32 draw fuses into the cast and is never materialised, so a
    stacked bf16 weight at 7B widths costs its own bytes and not a
    float32 copy twice its size beside it. Values are bit-for-bit those
    of the eager draw-then-scale-then-cast: ``floor`` is -inf at run
    time — an identity the compiler cannot see through, which keeps it
    from merging ``scale`` into the draw's own sqrt(2) factor (one
    rounding where the eager chain has two)."""
    return _seeded_normal(key, scale, -math.inf, shape=shape, dtype=dtype)


def init_params(key, cfg: DecoderConfig) -> Dict[str, Any]:
    L, D, F = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
    H, KV, dk = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    dt = cfg.dtype
    ks = jax.random.split(key, 10)
    std = 0.02

    def w(k, shape, scale=std):
        return seeded_normal(k, scale, shape=shape, dtype=dt)

    ones = lambda shape: jnp.ones(shape, dt)
    zeros = lambda shape: jnp.zeros(shape, dt)

    layers: Dict[str, Any] = {
        "attn_norm_scale": ones((L, D)),
        "wq": w(ks[0], (L, D, H * dk)),
        "wk": w(ks[1], (L, D, KV * dk)),
        "wv": w(ks[2], (L, D, KV * dk)),
        "wo": w(ks[3], (L, H * dk, D), std / math.sqrt(2 * L)),
    }
    E = cfg.num_local_experts
    if E:
        # expert-stacked FFN + router (HF Mixtral block_sparse_moe):
        # expert dim shards over the ``expert`` mesh axis
        Fe = cfg.moe_intermediate_size or F
        layers["w_router"] = w(jax.random.fold_in(ks[4], 1), (L, D, E))
        layers["w_up"] = w(ks[4], (L, E, D, Fe))
        layers["w_down"] = w(ks[5], (L, E, Fe, D), std / math.sqrt(2 * L))
        Fs = cfg.moe_shared_expert_intermediate_size
        if Fs:
            # always-on shared expert (Qwen2-MoE), sigmoid-gated; the
            # gate stays un-prefixed so quantization never touches it
            kk = jax.random.fold_in(ks[5], 7)
            layers["w_shared_up"] = w(jax.random.fold_in(kk, 0), (L, D, Fs))
            layers["w_shared_gate"] = w(jax.random.fold_in(kk, 1), (L, D, Fs))
            layers["w_shared_down"] = w(
                jax.random.fold_in(kk, 2), (L, Fs, D), std / math.sqrt(2 * L)
            )
            layers["shared_expert_gate"] = w(
                jax.random.fold_in(kk, 3), (L, D, 1)
            )
    else:
        layers["w_up"] = w(ks[4], (L, D, F))
        layers["w_down"] = w(ks[5], (L, F, D), std / math.sqrt(2 * L))
    if cfg.norm_bias:
        layers["attn_norm_bias"] = zeros((L, D))
    # Sequential blocks and Falcon-40B-style parallel blocks have a second
    # norm; Falcon-7B-style parallel blocks share one input norm.
    if (not cfg.parallel_block) or cfg.parallel_two_norms:
        layers["mlp_norm_scale"] = ones((L, D))
        if cfg.norm_bias:
            layers["mlp_norm_bias"] = zeros((L, D))
    if cfg.glu:
        layers["w_gate"] = w(
            ks[6],
            (L, E, D, cfg.moe_intermediate_size or F) if E else (L, D, F),
        )
    if cfg.qkv_bias:
        layers["bq"] = zeros((L, H * dk))
        layers["bk"] = zeros((L, KV * dk))
        layers["bv"] = zeros((L, KV * dk))
    if cfg.out_bias:
        layers["bo"] = zeros((L, D))
    if cfg.mlp_bias:
        layers["b_up"] = zeros((L, F))
        layers["b_down"] = zeros((L, D))
        if cfg.glu:
            layers["b_gate"] = zeros((L, F))

    params: Dict[str, Any] = {
        "embed": w(ks[7], (cfg.vocab_size, D)),
        "layers": layers,
        "final_norm_scale": ones((D,)),
    }
    if cfg.norm_bias:
        params["final_norm_bias"] = zeros((D,))
    if cfg.positions == "learned":
        params["pos_embed"] = w(
            ks[8], (cfg.max_position_embeddings + cfg.learned_pos_offset, D)
        )
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(ks[9], (D, cfg.vocab_size))
        if cfg.lm_head_bias:
            params["lm_head_bias"] = zeros((cfg.vocab_size,))
    return params


def param_pspecs(cfg: DecoderConfig, *, pipeline: bool = False) -> Dict[str, Any]:
    """Megatron TP shardings on ``model``; stacked layer dim on ``pipe``
    (the analog of the reference's hardcoded inference-TP rewrite,
    reference ``src/runtime/model.cc:3239-3312``)."""
    pp = PIPE_AXIS if pipeline else None
    col = lambda: P(pp, None, MODEL_AXIS)     # D×(sharded out)
    row = lambda: P(pp, MODEL_AXIS, None)     # (sharded in)×D
    vec_col = lambda: P(pp, MODEL_AXIS)       # bias of a col-parallel matmul
    vec_rep = lambda: P(pp, None)             # replicated per-layer vector

    layers = {
        "attn_norm_scale": vec_rep(),
        "wq": col(), "wk": col(), "wv": col(), "wo": row(),
        "w_up": col(), "w_down": row(),
    }
    if cfg.num_local_experts:
        # experts shard over the expert axis AND Megatron-TP inside each
        # expert (HF Mixtral weights are per-expert dense matmuls)
        layers["w_router"] = P(pp, None, None)
        layers["w_up"] = P(pp, EXPERT_AXIS, None, MODEL_AXIS)
        layers["w_down"] = P(pp, EXPERT_AXIS, MODEL_AXIS, None)
        if cfg.moe_shared_expert_intermediate_size:
            # the shared expert is dense per token: plain Megatron TP
            layers["w_shared_up"] = col()
            layers["w_shared_gate"] = col()
            layers["w_shared_down"] = row()
            layers["shared_expert_gate"] = P(pp, None, None)
    opt_specs = {
        "attn_norm_bias": vec_rep(),
        "mlp_norm_scale": vec_rep(),
        "mlp_norm_bias": vec_rep(),
        "w_gate": (
            P(pp, EXPERT_AXIS, None, MODEL_AXIS)
            if cfg.num_local_experts else col()
        ),
        "bq": vec_col(), "bk": vec_col(), "bv": vec_col(),
        "bo": vec_rep(),
        "b_up": vec_col(), "b_gate": vec_col(), "b_down": vec_rep(),
    }
    probe = init_shapes(cfg)
    for name, spec in opt_specs.items():
        if name in probe["layers"]:
            layers[name] = spec
    specs: Dict[str, Any] = {
        "embed": P(None, None),
        "layers": layers,
        "final_norm_scale": P(None),
    }
    if "final_norm_bias" in probe:
        specs["final_norm_bias"] = P(None)
    if "pos_embed" in probe:
        specs["pos_embed"] = P(None, None)
    if "lm_head" in probe:
        specs["lm_head"] = P(None, MODEL_AXIS)
    if "lm_head_bias" in probe:
        specs["lm_head_bias"] = P(MODEL_AXIS)
    return specs


@functools.lru_cache(maxsize=32)
def _shapes_cache(cfg: DecoderConfig):
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def init_shapes(cfg: DecoderConfig):
    return _shapes_cache(cfg)


# ---------------------------------------------------------------------------
# Attention + block (shared by train and serve paths)


def _gqa_attend(cfg: DecoderConfig, q, k, v, bias, mask):
    """q (B,S,H,dk) vs k/v (B,T,KV,dk) grouped without materialising the
    head repeat. ``bias`` (B,H,S,T) f32 or None; ``mask`` (B,S,T) bool."""
    B, S, H, dk = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, dk)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32
    ) / math.sqrt(cfg.head_dim)
    if bias is not None:
        scores = scores + bias.reshape(B, KV, G, *bias.shape[-2:])
    if mask is not None:
        m = mask if mask.ndim == 3 else mask[None]
        scores = jnp.where(m[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H * dk)


def _project_qkv(cfg: DecoderConfig, p, h):
    B, S, _ = h.shape
    H, KV, dk = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _mm(h, p["wq"])
    k = _mm(h, p["wk"])
    v = _mm(h, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (
        q.reshape(B, S, H, dk),
        k.reshape(B, S, KV, dk),
        v.reshape(B, S, KV, dk),
    )


@sublayer("attn.proj")
def _project_rope(cfg: DecoderConfig, p, h, rope):
    """:func:`_project_qkv`, with RoPE on the queries and keys where
    the family has it (``rope`` (cos, sin) or None)."""
    q, k, v = _project_qkv(cfg, p, h)
    if rope is not None:
        cos, sin = rope
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    return q, k, v


@sublayer("attn.proj")
def _rope_of(cfg: DecoderConfig, positions):
    """A step's (cos, sin) where the family has RoPE, else None."""
    return rope_freqs(cfg, positions) if cfg.positions == "rope" else None


@sublayer("attn.proj")
def _project_out(cfg: DecoderConfig, p, attn):
    attn = _mm(attn, p["wo"])
    if cfg.out_bias:
        attn = attn + p["bo"]
    return attn


@sublayer("moe.route")
def route_softmax_topk(h, w_router, k: int, *, norm_topk: bool = True,
                       offset=None, scaling: float = 1.0):
    """A linear router with a softmax (HF ``MixtralSparseMoeBlock``,
    ``Qwen2MoeSparseMoeBlock``), in float32: ``lax.top_k`` of the
    logits ``h W_r`` CHOOSES (among equals the lower index first); the
    weights are the softmax over the chosen k (``norm_topk``, Mixtral),
    or the chosen entries of the softmax over all experts, verbatim
    (Qwen2-MoE's ``norm_topk_prob=False``). h (..., D) -> (experts
    (..., k) int32, weights (..., k) float32). Beside
    :func:`route_sigmoid_topk`.

    ``offset`` (E,) float32, a selection offset on the SCORES
    (models/longcat_flash.py): the ``k`` largest of ``p + offset`` are
    chosen, ``p`` the softmax over all the router's outputs; the offset
    chooses and does not weigh: the weights are the chosen outputs' own
    ``p``, over their sum with ``norm_topk``. ``scaling``: a constant
    on the weights. None and 1: the function traces as it did before
    it had the arguments."""
    router = jnp.matmul(
        h.astype(jnp.float32), _dense_w(w_router, jnp.float32),
        preferred_element_type=jnp.float32,
    )  # (..., E)
    if offset is not None:
        p = jax.nn.softmax(router, axis=-1)
        _, topi = lax.top_k(p + offset.astype(jnp.float32), k)
        gate = jnp.take_along_axis(p, topi, axis=-1)
        if norm_topk:
            gate = gate / gate.sum(axis=-1, keepdims=True)
        return topi.astype(jnp.int32), gate * scaling
    topv, topi = lax.top_k(router, k)
    if norm_topk:
        gate = jax.nn.softmax(topv, axis=-1)
    else:
        gate = jnp.take_along_axis(
            jax.nn.softmax(router, axis=-1), topi, axis=-1
        )
    return topi, gate if scaling == 1.0 else gate * scaling


@sublayer("ffn")
def _shared_expert(cfg: DecoderConfig, p, h):
    """Qwen2-MoE's always-on shared expert, scaled by a sigmoid token
    gate (HF ``Qwen2MoeSparseMoeBlock`` shared_expert +
    shared_expert_gate): dense matmuls over every token."""
    s_up = _mm(h, p["w_shared_up"])
    s_act = _activation(cfg, _mm(h, p["w_shared_gate"])) * s_up
    s_out = _mm(s_act, p["w_shared_down"])
    s_gate = jax.nn.sigmoid(
        jnp.matmul(
            h.astype(jnp.float32),
            _dense_w(p["shared_expert_gate"], jnp.float32),
            preferred_element_type=jnp.float32,
        )
    ).astype(h.dtype)  # (..., 1)
    return s_gate * s_out


def _moe_ffn(cfg: DecoderConfig, p, h):
    """Mixtral-style sparse-MoE FFN (HF ``MixtralSparseMoeBlock``):
    linear router → top-k per token → softmax over the SELECTED k
    (:func:`route_softmax_topk`) → weighted sum of expert outputs, every
    expert computed for every position as one batched einsum over the
    expert dim. For E=8, K=2 that is E/K = 4x the routed FLOPs, and
    every expert's weights read every step.

    Still taken by :func:`forward` (training: the expert dim shards over
    the ``expert`` mesh axis, each device computes its expert range and
    GSPMD inserts the combine reduction, the serving-time analog of
    ops/moe.py's ExpertsOp range sharding), by the dense-layout
    :func:`serve_step`, by the paged step on a mesh of more than one
    device (no benchmark cell runs these, and a Mosaic kernel under
    GSPMD needs the ``shard_map`` that ``experts_held`` was written for,
    ROADMAP B0/B1) and by a paged step whose pairs are under 16 an
    expert (the C=1 step: both forms read every expert there, and
    the einsum is 3% the faster step). Every other paged serving step
    on one device routes its tokens instead (:func:`routes_tokens`,
    :func:`_routed_ffn`, :func:`routed_experts_ffn`)."""
    E, K = cfg.num_local_experts, cfg.num_experts_per_tok
    topi, gate = route_softmax_topk(
        h, p["w_router"], K, norm_topk=cfg.moe_norm_topk)  # (B,S,K)
    with sublayer("moe.route"):
        combine = jnp.einsum(
            "bsk,bske->bse", gate, jax.nn.one_hot(topi, E, dtype=jnp.float32)
        )  # (B,S,E)
    w_up = _dense_w(p["w_up"], h.dtype)
    w_down = _dense_w(p["w_down"], h.dtype)
    up = jnp.einsum(
        "bsd,edf->bsef", h, w_up, preferred_element_type=jnp.float32
    ).astype(h.dtype)
    if cfg.glu:
        gate_p = jnp.einsum(
            "bsd,edf->bsef", h, _dense_w(p["w_gate"], h.dtype),
            preferred_element_type=jnp.float32,
        ).astype(h.dtype)
        act = _activation(cfg, gate_p) * up
    else:
        act = _activation(cfg, up)
    # single contraction: folding the combine weights in avoids ever
    # materializing the E-times-wider (B,S,E,D) f32 intermediate
    out = jnp.einsum(
        "bsef,efd,bse->bsd", act, w_down, combine,
        preferred_element_type=jnp.float32,
    ).astype(h.dtype)
    if cfg.moe_shared_expert_intermediate_size:
        out = out + _shared_expert(cfg, p, h)
    return out


@sublayer("moe.route")
def route_sigmoid_topk(h, w_router, select_offset, k: int, *,
                       norm_topk: bool = True, scaling: float = 1.0,
                       groups: Tuple[int, int] = (1, 1), eps: float = 1e-6):
    """A sigmoid router with a selection offset (HF ``Lfm2MoeSparseMoeBlock``,
    DeepSeek-V3's rule): scores ``s = sigmoid(h W_r)`` in float32; the
    ``k`` largest of ``t = s + select_offset`` are CHOSEN (the offset
    chooses, it does not weigh; among equals the lower index first);
    the weights are the chosen experts' own ``s``, with ``norm_topk``
    divided by their sum plus ``eps`` (LFM2's 1e-6; DeepSeek-V3's is
    1e-20), times ``scaling``.

    ``groups`` (n_group, topk_group), DeepSeek-V3's choice by groups:
    the router's outputs are ``n_group`` equal runs; a group's score is
    the sum of its two largest ``t``; the ``topk_group`` best groups
    stay (among equals the lower index first) and the ``k`` experts are
    chosen among theirs alone. (1, 1): one group, every expert stays,
    and the function traces as it did before it had the argument.

    h (T, D) -> (experts (T, k) int32, weights (T, k) float32)."""
    s = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), _dense_w(w_router, jnp.float32),
        preferred_element_type=jnp.float32))
    choose = s if select_offset is None else s + select_offset.astype(jnp.float32)
    n_group, topk_group = groups
    if n_group > 1:
        T, E = choose.shape
        grouped = choose.reshape(T, n_group, E // n_group)
        score = lax.top_k(grouped, 2)[0].sum(axis=-1)            # (T, n_group)
        _, kept = lax.top_k(score, topk_group)
        stays = jnp.sum(jax.nn.one_hot(kept, n_group, dtype=jnp.int32), axis=1) > 0
        choose = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(T, E)
    _, experts = lax.top_k(choose, k)
    weights = jnp.take_along_axis(s, experts, axis=-1)
    if norm_topk:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + eps)
    return experts.astype(jnp.int32), weights * scaling


def routed_tile(tokens: int, k: int, experts_held: Tuple[int, int],
                routed=None) -> int:
    """The row tile of the grouped expert matmuls where ``tokens``
    places each choose ``k`` of the router's ``routed`` outputs (None:
    the experts held are all of them) and the range ``experts_held`` of
    them is here: serve/kernels ``grouped_tile`` at the static pairs.
    Asked by :func:`routed_experts_ffn` as it traces and by the engine
    for the step it dispatches (``InferenceEngine.step_tile``), both
    with what the family's ``expert_routing(cfg)`` declares, so that
    the host counts the tiles a step's tokens per expert fill
    (``SchedulerStats.note_expert_counts``) under the tile the step
    ran."""
    from ..serve.kernels import grouped_tile

    lo, hi = experts_held
    return grouped_tile(tokens * k, hi - lo, routed)


#: pairs a block of :func:`pair_layout`'s running count: the rows of one
#: pass through the MXU, and few enough that a count is exact in bfloat16
_COUNT_BLOCK = 128


@functools.partial(jax.jit, static_argnames=("n", "tm", "k"))
def pair_layout(group, n: int, tm: int, k: int):
    """Where the grouped matmuls' rows lie, BY COUNTING: ``group`` (P,)
    int32 names each (token, expert) pair's expert among the ``n`` held,
    pair ``p`` being token ``p // k``'s, and ``n`` itself a pair that is
    in no group. Every expert's rows start at a multiple of the row tile
    ``tm`` (1: the rows lie end to end) and keep the pairs' own order, so
    a pair's row is its expert's first row plus the pairs of the same
    expert BEFORE it: what a stable sort by expert gives, with no sort.
    That count is the exclusive running sum of the pairs' one-hot down
    the pairs: inside a block of :data:`_COUNT_BLOCK` pairs one matmul
    against the strictly lower triangle (0 / 1 in bfloat16, sums of at
    most 127 in float32: exact), and across the blocks a running sum of
    the blocks' totals (int32, P / 128 rows): work linear in the pairs.

    -> (``counts`` (n,) the pairs of each expert; ``place`` (P,) each
    pair's row, 0 for a pair in no group; ``source`` (rows,) the token
    of each row, ONE scatter of the pairs' tokens at their places (no
    two pairs share a row), 0 in the rows no pair has; ``ends`` (n,)
    the row after each expert's last aligned row), all int32, ``rows =
    tiles * tm`` the static bound ``P + n * (tm - 1)`` in whole tiles."""
    P, B = group.shape[0], _COUNT_BLOCK
    blocks = -(-P // B)
    rows = -(-(P + n * (tm - 1)) // tm) * tm
    hot = jax.nn.one_hot(
        jnp.pad(group, (0, blocks * B - P), constant_values=n).reshape(blocks, B),
        n, dtype=jnp.bfloat16)                                   # (blocks, B, n)
    before = jnp.einsum(
        "ij,bjn->bin", jnp.tril(jnp.ones((B, B), jnp.bfloat16), -1), hot,
        preferred_element_type=jnp.float32).astype(jnp.int32)   # in the block
    totals = jnp.sum(hot, axis=1, dtype=jnp.float32).astype(jnp.int32)
    upto = jnp.cumsum(totals, axis=0)                            # (blocks, n)
    counts = upto[-1]
    aligned = -(-counts // tm) * tm
    ends = jnp.cumsum(aligned)
    first = (ends - aligned) + (upto - totals)    # an expert's first row, a block
    place = jnp.sum(jnp.where(hot > 0, before + first[:, None, :], 0),
                    axis=-1).reshape(-1)[:P]
    # a pair in no group goes past the rows, and is dropped
    source = jnp.zeros((rows,), jnp.int32).at[
        jnp.where(group < n, place, rows)].set(
            jnp.arange(P, dtype=jnp.int32) // k, mode="drop")
    return counts, place, source, ends


@jax.jit
def pairs_to_tokens(rows, place, held, weights):
    """The experts' results brought back: ``rows`` (R, D) float32 by
    aligned row, ``place`` (T, k) each pair's row, ``held`` (T, k)
    whether the pair has a result, ``weights`` (T, k) -> (T, D)
    float32, token t's ``sum_j weights[t, j] rows[place[t, j]]`` over
    its held pairs. Each product and each addition is float32, and the
    terms are ADDED IN CHOICE ORDER, j = 0, 1, ..., k - 1, left to
    right: that order is the layer's bits (tests/test_moe.py pins it).
    The rows are gathered once, CHOICE-MAJOR (by ``place.T``), so that
    what the sum reads is (k, T, D) with the tokens on the sublanes and
    D on the lanes: a token-major (T, k, D) pads k to the 8 sublanes
    wherever it is no multiple of them, a copy and a padded read a
    layer (PERF.md section 6, PR 59). A pair in no group has no result:
    that is said here, by the mask, and not by what a grouped matmul
    leaves in rows it never wrote."""
    T, k = place.shape
    got = jnp.take(rows, place.T.reshape(-1), axis=0,
                   mode="clip").reshape(k, T, -1)
    held, weights = held.T, jnp.where(held, weights, 0.0).T
    # a choice's slice of the gathered rows times its weights, term by
    # term: one fusion that reads (k, T, D) once. Slices of an array of
    # products would keep that array beside the gathered one.
    return functools.reduce(jnp.add, (
        jnp.where(held[j, :, None], got[j], 0.0) * weights[j, :, None]
        for j in range(k)))


@functools.partial(jax.jit, static_argnames=("tiles", "tm"))
def tile_experts(ends, tiles: int, tm: int):
    """-> (``tile_group`` (tiles,) the expert each ``tm``-row tile of
    :func:`pair_layout`'s rows belongs to, ``n_active`` the tiles up to
    the last expert's last row), int32, from ``ends`` (n,). A tile's
    expert is the number of experts whose rows end at or before the
    tile's first row (a compare and a sum: ``searchsorted`` with no
    loop); the tiles past ``n_active``, which the grouped matmuls skip,
    repeat the last active tile's, so that they ask for no other
    weight block."""
    n_active = ends[-1] // tm
    tile = jnp.arange(tiles, dtype=jnp.int32)
    tile_group = jnp.sum(ends[None, :] <= (tile * tm)[:, None], axis=1,
                         dtype=jnp.int32)
    last = tile_group[jnp.maximum(n_active - 1, 0)]
    return jnp.minimum(jnp.where(tile < n_active, tile_group, last),
                       ends.shape[0] - 1), n_active


@sublayer("moe.route")
def routed_experts_ffn(h, real, experts, weights, w_gate, w_up, w_down, *,
                       experts_held: Tuple[int, int], routed=None,
                       layer=None, kernels: str = "xla",
                       activation: str = "silu"):
    """The routed half of a sparse FFN as a GROUPED matmul: the (token,
    expert) pairs of real tokens laid out by expert, one grouped matmul
    a projection over the groups, the pairs' results weighted and summed
    back by token. Its FLOPs follow the rows, not rows x experts, and it
    reads the weights of the experts that have rows. Beside
    :func:`_moe_ffn`, which computes every expert for every position.
    Taken by the paged serving steps on one device: ``models/lfm2_moe.py``
    at every width and, for ``mixtral`` and ``qwen2_moe``,
    :func:`serve_step_paged` from 16 pairs an expert on
    (:func:`routes_tokens`, :func:`_routed_ffn`).

    The layout is built by counting (:func:`pair_layout`: no sort, one
    scatter of the pairs' tokens) and each pair's row moves once in (one
    gather of ``h`` by ``source``) and once out (one gather of the
    experts' results by ``place``, choice-major, weighted and summed by
    token in choice order: :func:`pairs_to_tokens`). A row
    of the layout that no pair has (the alignment to the tile, the
    static bound's tail) holds token 0's row and not zeros: the grouped
    matmuls compute it where its tile is active and NOTHING reads its
    result, since ``place`` names real pairs' rows alone and a pair in
    no group is masked after the gather; a pass that zeroed those rows
    wrote and read the whole aligned array once more a layer (PERF.md
    section 6, PR 57).

    ``kernels="xla"``: ``lax.ragged_dot`` over the rows at a tile of one
    (on the chip the compiler's own grouped-matmul kernel). ``"pallas"``:
    serve/kernels ``grouped_glu`` / ``grouped_down``
    (``ff_moe_grouped_*``), for which every expert's rows start at a
    multiple of the row tile, so a tile has one expert, named in the
    weight blocks' index map; tiles past the last row are skipped. The
    tile follows from the static pairs, the experts held and the
    router's outputs (:func:`routed_tile`).

    h (T, D); ``real`` (T,) bool: padding places route nowhere;
    ``experts`` / ``weights`` (T, k) the router's choice
    (:func:`route_sigmoid_topk` or :func:`route_softmax_topk`, over ALL
    its outputs). ``experts_held``
    (lo, hi): the range of the router's outputs whose weights are here
    (``w_gate`` / ``w_up`` (hi - lo, D, F), ``w_down`` (hi - lo, F, D));
    the result is that range's part of the layer's, so the parts of
    ranges that cover the router add up to the whole layer (the
    model-configs guide's usual cut: a chip holds some experts of each
    layer and computes its own part); ``routed`` the router's outputs
    where the range is a part of them (static; None: the range is all
    of them), from which the row tile reckons the rows an expert is
    given. ``layer``: the weights are every
    layer's, stacked (L, hi - lo, ...), and this call addresses its own
    experts inside the free (L * (hi - lo), ...) view (the Pallas path
    by an offset on the tiles' expert index, the XLA path by giving
    every other layer's experts an empty group): a layer sliced out of
    the stack would be a copy of its weights a step, since no slice
    fuses into a kernel call. ``activation`` (static): the gate's, a
    name of ``jax.nn``: ``silu`` where nothing is said, ``relu`` for
    ReGLU experts.

    Returns (out (T, D) in h's dtype, counts (hi - lo,) int32: the real
    tokens each held expert was given)."""
    T, k = experts.shape
    lo, hi = experts_held
    n = hi - lo
    held = real[:, None] & (experts >= lo) & (experts < hi)
    group = jnp.where(held, experts - lo, n).reshape(-1)   # n: in no group
    first = 0
    if layer is not None:
        first = layer * n
        w_gate, w_up, w_down = (
            w.reshape((-1,) + w.shape[2:]) for w in (w_gate, w_up, w_down))
    w_gate, w_up, w_down = (_dense_w(w, h.dtype) for w in (w_gate, w_up, w_down))
    tm = routed_tile(T, k, experts_held, routed) if kernels == "pallas" else 1
    counts, place, source, ends = pair_layout(group, n, tm, k)
    rows = h.at[source].get(mode="promise_in_bounds")
    if kernels == "pallas":
        from ..serve import kernels as _pk

        tile_group, n_active = tile_experts(ends, source.shape[0] // tm, tm)
        tile_group = first + tile_group
        with sublayer("ffn"):
            # the weight fetches' scalars, once for the layer's two calls
            fetches = _pk.grouped_fetches(tile_group, n_active)
            act = _pk.grouped_glu(rows, w_gate, w_up, tile_group, n_active,
                                  tm=tm, activation=activation,
                                  fetches=fetches)
            out = _pk.grouped_down(act, w_down, tile_group, n_active, tm=tm,
                                   fetches=fetches)
    else:
        sizes = counts
        if layer is not None:
            sizes = lax.dynamic_update_slice(
                jnp.zeros((w_gate.shape[0],), jnp.int32), counts, (first,))
        dot = functools.partial(lax.ragged_dot, group_sizes=sizes,
                                preferred_element_type=jnp.float32)
        with sublayer("ffn"):
            act = (getattr(jax.nn, activation)(dot(rows, w_gate))
                   * dot(rows, w_up)).astype(h.dtype)
            out = dot(act, w_down)                           # (P, D) float32
    out = pairs_to_tokens(out, place.reshape(T, k), held, weights)
    return out.astype(h.dtype), counts


@sublayer("ffn")
def _ffn(cfg: DecoderConfig, p, h):
    if cfg.num_local_experts:
        return _moe_ffn(cfg, p, h)
    up = _mm(h, p["w_up"])
    if cfg.mlp_bias:
        up = up + p["b_up"]
    if cfg.glu:
        gate = _mm(h, p["w_gate"])
        if cfg.mlp_bias:
            gate = gate + p["b_gate"]
        act = _activation(cfg, gate) * up
    else:
        act = _activation(cfg, up)
    out = _mm(act, p["w_down"])
    if cfg.mlp_bias:
        out = out + p["b_down"]
    return out


#: the experts' leaves of a layer's weights: what a routed step keeps
#: stacked over the layers (``layer_weights``' ``whole``)
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def routes_tokens(cfg: DecoderConfig, layers, tokens: int) -> bool:
    """Whether a paged serving step over ``tokens`` places sends its
    real tokens through :func:`routed_experts_ffn` rather than
    :func:`_moe_ffn`, from what the step can see: a sparse layer of the
    form the grouped matmuls compute (a SiLU GLU over plain,
    unquantized expert stacks); an ambient mesh of one device (under
    GSPMD the grouped Pallas calls need a ``shard_map`` over the
    experts held, ROADMAP B0/B1; the einsum shards as it is); and
    static pairs that are 16 an expert, a bf16 sublane tile of rows
    each, whatever row tile the grouped matmuls then take
    (serve/kernels ``grouped_tile``: 32 at Mixtral's admission rung of
    512 pairs) and however they fetch their weights (a run of tiles
    ahead since PR 52, ``grouped_fetches``). Under that (the
    C=1 step of 16 slots: 32 pairs, 4 an expert) both forms read every
    expert's weights for a handful of rows, and routing, sorting and
    gathering the pairs only add to the step: 17.40 ms against the
    einsum's 16.91 on a v5e at Mixtral's widths (PERF.md, PR 36)."""
    mesh = jax.sharding.get_abstract_mesh()
    experts, pairs = cfg.num_local_experts, tokens * cfg.num_experts_per_tok
    return bool(
        experts and cfg.glu and cfg.activation == "silu"
        and not any(isinstance(layers[name], dict) for name in EXPERT_STACKS)
        and (mesh.empty or mesh.size == 1)
        and pairs >= 16 * experts
    )


def expert_routing(cfg: DecoderConfig) -> Tuple[int, Tuple[int, int], int]:
    """What the row tile of the grouped expert matmuls is reckoned from
    beside a step's places (:func:`routed_tile`), declared once a
    family beside :func:`step_counts`: (the experts a token chooses,
    the range of experts held, the router's outputs). Read by the
    family's own call of :func:`routed_experts_ffn` and by the engine
    (``InferenceEngine.step_tile``)."""
    return (cfg.num_experts_per_tok, (0, cfg.num_local_experts),
            cfg.num_local_experts)


def step_counts(cfg: DecoderConfig) -> Dict[str, Tuple[int, ...]]:
    """What :func:`serve_step_paged` returns in its cache that is no
    state (name -> shape, int32; the engine takes these out and hands
    them to the scheduler behind the sampled tokens, as for
    ``models/lfm2_moe.py``). ``moe_counts``: each layer's real tokens
    per expert where the step routed its tokens (:func:`routes_tokens`),
    zeros where it took the all-expert einsum. A dense model: nothing."""
    if not cfg.num_local_experts:
        return {}
    return {"moe_counts": (cfg.num_hidden_layers, cfg.num_local_experts)}


def _routed_ffn(cfg: DecoderConfig, p, h, real, layer, kernels: str):
    """The sparse FFN of the paged serving step with its tokens ROUTED:
    :func:`_moe_ffn`'s router, then the (token, expert) pairs of the
    real tokens through the grouped matmuls; the shared expert stays
    dense. h (B, S, D); ``real`` (B*S,); ``p``'s ``EXPERT_STACKS`` are
    every layer's, addressed by ``layer``. -> (out (B, S, D), counts
    (E,) int32 the real tokens each expert was given)."""
    B, S, D = h.shape
    flat = h.reshape(B * S, D)
    experts, weights = route_softmax_topk(
        flat, p["w_router"], cfg.num_experts_per_tok,
        norm_topk=cfg.moe_norm_topk)
    _, held, routed = expert_routing(cfg)
    out, counts = routed_experts_ffn(
        flat, real, experts, weights, *(p[name] for name in EXPERT_STACKS),
        experts_held=held, routed=routed, layer=layer, kernels=kernels)
    out = out.reshape(B, S, D)
    if cfg.moe_shared_expert_intermediate_size:
        out = out + _shared_expert(cfg, p, h)
    return out, counts


def block(
    cfg: DecoderConfig,
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,              # (B, S, D)
    rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]],
    bias: Optional[jnp.ndarray],  # additive attention bias (ALiBi)
    mask: Optional[jnp.ndarray],
    attn_fn=None,
):
    """One decoder block, full-sequence (training) attention; the
    serving blocks with a KV cache are :func:`serve_block` and
    :func:`serve_block_paged`. ``attn_fn(cfg, q, k, v, mask)`` ->
    (B, S, H, dk) computes the attention in place of
    :func:`_gqa_attend` (:func:`make_flash_attention`,
    :func:`make_sp_attention`): it is given K/V compact (KV heads, not
    H) and derives causality itself. Returns (x_out, None): the None
    keeps the scan-body signature of the serving blocks."""
    h = _norm(cfg, x, p["attn_norm_scale"], p.get("attn_norm_bias"))
    q, k, v = _project_qkv(cfg, p, h)
    if rope is not None:
        cos, sin = rope
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if attn_fn is None:
        attn = _gqa_attend(cfg, q, k, v, bias, mask)
    else:
        attn = attn_fn(cfg, q, k, v, mask).reshape(*x.shape[:2], -1)
    attn = _mm(attn, p["wo"])
    if cfg.out_bias:
        attn = attn + p["bo"]

    if cfg.parallel_block:
        if cfg.parallel_two_norms:
            h2 = _norm(cfg, x, p["mlp_norm_scale"], p.get("mlp_norm_bias"))
        else:
            h2 = h
        return x + attn + _ffn(cfg, p, h2), None
    x = x + attn
    h2 = _norm(cfg, x, p["mlp_norm_scale"], p.get("mlp_norm_bias"))
    return x + _ffn(cfg, p, h2), None


def _train_bias(cfg: DecoderConfig, positions):
    """ALiBi additive bias for full-sequence attention: (B,H,S,S)."""
    if cfg.positions != "alibi":
        return None
    slopes = alibi_slopes(cfg.num_attention_heads)
    qp = positions.astype(jnp.float32)
    dist = qp[:, None, :, None] - qp[:, None, None, :]  # (B,1,S,S) q - k
    return -slopes[None, :, None, None] * dist


def _embed_in(cfg: DecoderConfig, params, tokens, positions):
    x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
    if cfg.embed_scale:  # Gemma scales inputs by sqrt(hidden)
        x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)
    if cfg.positions == "learned":
        # mode="clip": padding slots carry the scratch-row position, which
        # exceeds the table; JAX's default out-of-bounds fill is NaN, which
        # would poison attention through the scratch cache line.
        x = x + jnp.take(
            params["pos_embed"],
            positions.astype(jnp.int32) + cfg.learned_pos_offset,
            axis=0,
            mode="clip",
        )
    return x


@sublayer("head")
def _lm_logits(cfg: DecoderConfig, params, x):
    if cfg.tie_word_embeddings:
        # x embed^T as a contraction over the embedding's own minor
        # axis: a transposed (V, D) table would be written out a step
        logits = lax.dot_general(
            x, params["embed"], (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        logits = jnp.matmul(x, params["lm_head"],
                            preferred_element_type=jnp.float32)
    if "lm_head_bias" in params:
        logits = logits + params["lm_head_bias"].astype(jnp.float32)
    return logits


@sublayer("head")
def _head_logits(cfg: DecoderConfig, params, x, logits_idx, pack, all_logits):
    """The end of a step: the final norm over the token axis, each
    row's hidden state at its ``logits_idx`` (with ``pack``,
    :func:`_pack_tokens`' second result, at that column's packed place)
    unless ``all_logits``, and the LM head. -> (R, V), or (R, C, V)
    with ``all_logits``."""
    x = _norm(cfg, x, params["final_norm_scale"], params.get("final_norm_bias"))
    if pack is None and all_logits:
        return _lm_logits(cfg, params, x)
    if pack is None:
        x = jnp.take_along_axis(x, logits_idx[:, None, None], axis=1)
    else:
        # row r samples from the packed place of its column logits_idx[r]
        at = jnp.take_along_axis(pack[0], logits_idx[:, None], axis=1)
        x = jnp.take(x[0], at, axis=0, mode="clip")
    return _lm_logits(cfg, params, x)[:, 0]


def _full_sequence_context(cfg: DecoderConfig, positions, attn_fn):
    """(rope, bias, mask) of a full-sequence pass over ``positions``
    (..., S). With an ``attn_fn`` there is no (S, S) mask to build: the
    override derives causality from the positions and never
    materialises it (the long-context path), and knows neither an
    additive bias nor a window."""
    rope = rope_freqs(cfg, positions) if cfg.positions == "rope" else None
    if attn_fn is not None:
        if cfg.positions == "alibi" or cfg.sliding_window:
            raise ValueError(
                "an attention override (flash / sequence-parallel) is "
                "plain causal attention: it carries no ALiBi bias and no "
                "sliding window"
            )
        return rope, None, None
    S = positions.shape[-1]
    mask = jnp.tril(jnp.ones((S, S), bool))
    if cfg.sliding_window:
        idx = jnp.arange(S)
        mask &= idx[None, :] > idx[:, None] - cfg.sliding_window
    return rope, _train_bias(cfg, positions), mask


def _block_fn(cfg: DecoderConfig, attn_fn, remat: bool,
              remat_policy: Optional[str]):
    """:func:`block` bound to its config, under ``jax.checkpoint`` with
    ``remat`` (``remat_policy``: core/remat.py)."""
    blk = functools.partial(block, cfg, attn_fn=attn_fn)
    if remat:
        from ..core.remat import resolve_remat_policy

        blk = jax.checkpoint(blk, policy=resolve_remat_policy(remat_policy))
    return blk


def forward(
    params: Dict[str, Any],
    tokens: jnp.ndarray,  # (B, S) int32
    cfg: DecoderConfig,
    *,
    positions: Optional[jnp.ndarray] = None,
    remat: bool = False,
    remat_policy: Optional[str] = None,
    shard_activations: bool = False,
    attn_fn=None,
) -> jnp.ndarray:
    """Training/eval forward: full causal attention → logits (B, S, V).
    ``attn_fn`` overrides the attention computation (:func:`block`)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _embed_in(cfg, params, tokens, positions)
    rope, bias, mask = _full_sequence_context(cfg, positions, attn_fn)

    def constrain(t):
        if shard_activations:
            return lax.with_sharding_constraint(t, P(DATA_AXIS, SEQ_AXIS, None))
        return t

    x = constrain(x)
    blk = _block_fn(cfg, attn_fn, remat, remat_policy)

    def scan_body(carry, p_l):
        y, _ = blk(p_l, carry, rope, bias, mask)
        return constrain(y), None

    x, _ = lax.scan(scan_body, x, params["layers"])
    x = _norm(cfg, x, params["final_norm_scale"], params.get("final_norm_bias"))
    return _lm_logits(cfg, params, x)


def make_flash_attention(block_q: int = 128, block_k: int = 128):
    """Causal flash-attention ``attn_fn`` (Pallas kernel with custom VJP,
    ops/flash_attention.py): scores stream through VMEM instead of
    materialising the (B, H, S, S) tensor the XLA path writes to HBM."""
    from ..ops.flash_attention import flash_attention

    def attn_fn(cfg, q, k, v, mask):
        # the kernel takes one K/V head a query head
        rep = cfg.num_attention_heads // cfg.num_key_value_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k
        )

    return attn_fn


def make_sp_attention(mesh, impl: str = "ring"):
    """A sequence-parallel ``attn_fn`` (ring ppermute or Ulysses
    all-to-all over the ``seq`` axis — the long-context capability the
    reference lacks, SURVEY.md §7 step 7)."""
    from ..parallel.sequence import ring_attention, ulysses_attention

    fn = ring_attention if impl == "ring" else ulysses_attention

    def attn_fn(cfg, q, k, v, mask):
        # K/V stay compact (GQA/MQA); the SP primitives expand per block
        # so ring ppermute traffic is KV-sized, not H-sized.
        return fn(
            q, k, v, mesh, causal=True,
            shard_heads=mesh.shape[MODEL_AXIS] > 1,
        )

    return attn_fn


def _next_token_nll(logits, targets) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def next_token_loss(params, tokens, cfg: DecoderConfig, **kw) -> jnp.ndarray:
    """Causal LM loss: predict tokens[:, 1:] from tokens[:, :-1]."""
    logits = forward(params, tokens[:, :-1], cfg, **kw)
    return _next_token_nll(logits, tokens[:, 1:].astype(jnp.int32))


def make_train_step(
    cfg: DecoderConfig,
    mesh,
    optimizer,
    *,
    num_microbatches: int = 1,
    remat: bool = True,
    remat_policy: Optional[str] = None,  # None (full) | "dots"
    shard_activations: bool = True,
    attention: str = "xla",  # "xla" | "flash" (Pallas, ops/flash_attention)
):
    """Build (init_fn, step_fn, data_sharding) jitted over ``mesh`` with
    the full dp/tp/pp/sp sharding stack, for any :class:`DecoderConfig`.

    * dp: batch dim sharded on ``data`` (GSPMD all-reduces grads).
    * tp: Megatron weight shardings from :func:`param_pspecs` (GSPMD
      inserts the QKV/FFN all-reduces over ICI).
    * sp: activation sequence dim constrained to the ``seq`` axis.
    * pp (when mesh has pipe>1): GPipe microbatching via
      ``parallel.pipeline`` — the stacked layer dim is sharded over
      ``pipe`` and only that axis runs manually under shard_map.
    """
    from jax.sharding import NamedSharding

    pipeline = mesh.shape[PIPE_AXIS] > 1
    pspecs = param_pspecs(cfg, pipeline=pipeline)
    shardings = jax.tree.map(
        lambda p: NamedSharding(mesh, p), pspecs, is_leaf=lambda x: isinstance(x, P)
    )

    def init_fn(key):
        params = jax.jit(
            functools.partial(init_params, cfg=cfg), out_shardings=shardings
        )(key)
        opt_state = optimizer.init(params)
        return params, opt_state

    if not pipeline:
        sp = mesh.shape[SEQ_AXIS] > 1
        if sp:
            if attention == "flash":
                # explicit kernel choices must not be silently ignored
                from ..logging_utils import get_logger

                get_logger("model").warning(
                    "attention='flash' requested but the mesh has seq=%d: "
                    "sequence parallelism uses ring attention instead "
                    "(flash+SP composition is not implemented)",
                    mesh.shape[SEQ_AXIS],
                )
            attn_fn = make_sp_attention(mesh, "ring")
        elif attention == "flash":
            attn_fn = make_flash_attention()
        else:
            attn_fn = None

        def loss_fn(params, tokens):
            return next_token_loss(
                params,
                tokens,
                cfg,
                remat=remat,
                remat_policy=remat_policy,
                shard_activations=shard_activations and sp,
                attn_fn=attn_fn,
            )

    else:
        assert mesh.shape[SEQ_AXIS] == 1, (
            "sequence parallelism is not composed with the pipeline path "
            "yet: pipe>1 with seq>1 would fall back to dense attention "
            "over the gathered sequence (O(S^2) memory)"
        )
        from ..parallel.pipeline import make_pipelined_apply

        attn_fn = make_flash_attention() if attention == "flash" else None
        blk = _block_fn(cfg, attn_fn, remat, remat_policy)

        def loss_fn(params, tokens):
            B, S = tokens.shape
            mb = B // num_microbatches
            inp, targets = tokens[:, :-1], tokens[:, 1:].astype(jnp.int32)
            positions = jnp.broadcast_to(
                jnp.arange(S - 1, dtype=jnp.int32), (B, S - 1))
            x = _embed_in(cfg, params, inp, positions)
            # every microbatch holds the same positions
            rope, bias, mask = _full_sequence_context(
                cfg, positions[:mb], attn_fn)

            def block_stack(stage_layers, x_mb):
                def body(carry, p_l):
                    y, _ = blk(p_l, carry, rope, bias, mask)
                    return y, None

                y, _ = lax.scan(body, x_mb, stage_layers)
                return y

            piped = make_pipelined_apply(
                mesh,
                block_stack,
                num_microbatches=num_microbatches,
                params_spec=jax.tree.map(
                    lambda _: P(PIPE_AXIS), params["layers"]
                ),
            )
            y = piped(
                params["layers"],
                x.reshape(num_microbatches, mb, S - 1, cfg.hidden_size),
            ).reshape(B, S - 1, cfg.hidden_size)
            y = _norm(cfg, y, params["final_norm_scale"],
                      params.get("final_norm_bias"))
            return _next_token_nll(_lm_logits(cfg, params, y), targets)

    def step_fn(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, loss

    data_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    step = jax.jit(step_fn, donate_argnums=(0, 1))
    return init_fn, step, data_sharding


# ---------------------------------------------------------------------------
# Serving path (KV cache) — the engine's protocol (serve/engine.py). One
# step function serves prefill (chunk C>1), incremental decode (C=1) and
# SpecInfer tree-verify (explicit mask): the TPU-native counterpart of the
# reference's three attention operators
# (inc/spec/tree_inc_multihead_self_attention, SURVEY.md §2.1). Instead of
# three CUDA kernels there is one compiled XLA program per static
# (C, all_logits, mask-mode) signature, all sharing the same KV buffers.


def needs_pos_cache(cfg: DecoderConfig) -> bool:
    """ALiBi biases and sliding-window masks depend on key *sequence*
    positions at attention time (RoPE bakes position into cached K
    instead), so the cache carries a per-line position buffer. For the
    window this makes tree-verify masking EXACT: an in-flight tree key's
    cache line (prefix + node index) is not its sequence position
    (prefix + depth), so a line-index window would under-mask."""
    return cfg.positions == "alibi" or cfg.sliding_window > 0


def init_kv_cache(cfg: DecoderConfig, num_slots: int, max_len: int, dtype=None):
    """KV cache pytree: (L, slots, max_len+1, KV, dk). The last position
    is a scratch row — padding tokens scatter there so real cache lines
    are never corrupted (replaces the reference's per-request contiguous
    cache with request-slot paging,
    inc_multihead_self_attention.cu:1338). ALiBi / sliding-window
    configs add the (slots, max_len+1) position buffer
    (:func:`needs_pos_cache`)."""
    L, KV, dk = cfg.num_hidden_layers, cfg.num_key_value_heads, cfg.head_dim
    dt = dtype or cfg.dtype
    shape = (L, num_slots, max_len + 1, KV, dk)
    cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if needs_pos_cache(cfg):
        cache["pos"] = jnp.zeros((num_slots, max_len + 1), jnp.int32)
    return cache


def kv_cache_pspecs(cfg: DecoderConfig = None, *, pipeline: bool = False):
    # MQA (KV=1) caches replicate across TP: a size-1 head dim cannot
    # split over the model axis (the memory cost is the standard MQA
    # serving trade; queries still shard by head). With ``pipeline`` the
    # layer-major leading dim shards over ``pipe``.
    kv_axis = None if (cfg is not None and cfg.num_key_value_heads == 1) else MODEL_AXIS
    pp = PIPE_AXIS if pipeline else None
    specs = {
        "k": P(pp, DATA_AXIS, None, kv_axis, None),
        "v": P(pp, DATA_AXIS, None, kv_axis, None),
    }
    if cfg is not None and needs_pos_cache(cfg):
        specs["pos"] = P(DATA_AXIS, None)
    return specs


def _serve_attend(cfg: DecoderConfig, q, k_cache, v_cache, bias, mask,
                  scale: Optional[float] = None):
    """q (R,C,H,dk) against cache (R,S1,KV,dk). ``scale``: the softmax
    scale of a family that states its own (Granite's
    ``attention_multiplier``); None is 1/sqrt(head_dim)."""
    R, C, H, dk = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(R, C, KV, G, dk)
    scores = jnp.einsum(
        "rckgd,rskd->rkgcs", qg, k_cache, preferred_element_type=jnp.float32
    )
    if scale is None:
        scores = scores / math.sqrt(cfg.head_dim)
    else:
        scores = scores * scale
    if bias is not None:  # (R,H,C,S1)
        scores = scores + bias.reshape(R, KV, G, *bias.shape[-2:])
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("rkgcs,rskd->rckgd", probs, v_cache)
    return out.reshape(R, C, H * dk)


@sublayer("glue")
def serve_block(cfg, p, x, rope, bias, mask, k_cache, v_cache, cache_positions):
    R, C, D = x.shape
    h = _norm(cfg, x, p["attn_norm_scale"], p.get("attn_norm_bias"))
    q, k, v = _project_rope(cfg, p, h, rope)
    with sublayer("attn.write"):
        bidx = jnp.arange(R)[:, None]
        k_cache = k_cache.at[bidx, cache_positions].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, cache_positions].set(v.astype(v_cache.dtype))
    with sublayer("attn.core"):
        attn = _serve_attend(cfg, q, k_cache, v_cache, bias, mask)
    attn = _project_out(cfg, p, attn)
    if cfg.parallel_block:
        if cfg.parallel_two_norms:
            h2 = _norm(cfg, x, p["mlp_norm_scale"], p.get("mlp_norm_bias"))
        else:
            h2 = h
        return x + attn + _ffn(cfg, p, h2), k_cache, v_cache
    x = x + attn
    h2 = _norm(cfg, x, p["mlp_norm_scale"], p.get("mlp_norm_bias"))
    return x + _ffn(cfg, p, h2), k_cache, v_cache


@sublayer("glue")
def serve_step(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,      # (R, C)
    positions: jnp.ndarray,   # (R, C) sequence positions
    logits_idx: jnp.ndarray,  # (R,)
    mask: Optional[jnp.ndarray],   # (R, C, S1) bool or None => causal
    cache_positions: Optional[jnp.ndarray] = None,
    *,
    cfg: DecoderConfig,
    all_logits: bool = False,
    num_layers: Optional[int] = None,
    mesh=None,
):
    """One serving step over R request slots × C tokens each (the engine
    protocol, serve/engine.py). Padding tokens sit at the scratch
    position.

    ``cache_positions`` (cache line indices) defaults to ``positions``;
    SpecInfer passes them separately because sibling tree tokens share a
    sequence position (prefix + depth) but need distinct cache lines
    (prefix + node index).

    With a ``mesh`` whose pipe axis is >1, the layer stack (and the
    layer-major KV cache) is stage-sharded and activations flow through
    the pipeline (reference inference_manager.cc:91-133 stage mapping).

    ``num_layers`` runs a LAYER-SLICED step: only the first
    ``num_layers`` blocks execute (their K/V commit into the cache; the
    deeper layers' cache buffers pass through untouched; the position
    buffer, written once per step rather than per layer, updates in
    full either way) before the full model's final norm + head read the
    truncated hidden state — the self-speculation "early-exit" draft
    (LayerSkip-style, SpecConfig.draft="early_exit"): the target's own
    shallow prefix drafts tokens the full-depth verify pass then
    re-checks. None (default) = the full stack.

    Returns (logits, new_cache): logits (R, V) at ``logits_idx`` or
    (R, C, V) when ``all_logits`` (tree verification needs every token's
    logits, reference tree_inc_multihead_self_attention.cu)."""
    R, C = tokens.shape
    S1 = cache["k"].shape[2]
    if cache_positions is None:
        cache_positions = positions
    x = _embed_in(cfg, params, tokens, positions)
    rope = _rope_of(cfg, positions)
    if mask is None:
        from ..serve.kernels import causal_serve_mask

        mask = causal_serve_mask(positions, S1)

    bias = None
    pos_cache = None
    if needs_pos_cache(cfg):
        bidx = jnp.arange(R)[:, None]
        pos_cache = cache["pos"].at[bidx, cache_positions].set(
            positions.astype(jnp.int32)
        )
        if cfg.positions == "alibi":
            slopes = alibi_slopes(cfg.num_attention_heads)
            dist = (
                positions.astype(jnp.float32)[:, None, :, None]
                - pos_cache.astype(jnp.float32)[:, None, None, :]
            )  # (R,1,C,S1)
            bias = -slopes[None, :, None, None] * dist
    if cfg.sliding_window:
        # window by TRUE key sequence positions from the pos cache —
        # exact for every path, including tree-verify lines whose cache
        # line (prefix + node index) differs from their sequence
        # position (prefix + depth). Unwritten lines hold position 0,
        # but the causal/tree mask already excludes them.
        mask = mask & (
            pos_cache[:, None, :]
            > positions[:, :, None] - cfg.sliding_window
        )

    def scan_body(h, xs):
        p_l, kc, vc = xs
        h, kc, vc = serve_block(
            cfg, p_l, h, rope, bias, mask, kc, vc, cache_positions
        )
        return h, (kc, vc)

    if mesh is not None and mesh.shape[PIPE_AXIS] > 1:
        if num_layers is not None:
            raise NotImplementedError(
                "early-exit drafting (num_layers) is not composed with "
                "pipeline parallelism — the sliced stack would idle the "
                "deeper stages"
            )

        from ..parallel.pipeline import make_pipelined_serve

        # Row-sharded args go through explicit specs (closures would
        # replicate over the manual data axis — see make_pipelined_serve).
        row = {"mask": mask, "cpos": cache_positions}
        if rope is not None:
            row["cos"], row["sin"] = rope
        if bias is not None:
            row["bias"] = bias

        def stage_fn(stage_layers, caches, h, row):
            rope_l = (row["cos"], row["sin"]) if "cos" in row else None
            kc, vc = caches

            def body(hh, xs):
                p_l, kcl, vcl = xs
                hh, kcl, vcl = serve_block(
                    cfg, p_l, hh, rope_l, row.get("bias"), row["mask"],
                    kcl, vcl, row["cpos"],
                )
                return hh, (kcl, vcl)

            h, (kc, vc) = lax.scan(body, h, (stage_layers, kc, vc))
            return h, (kc, vc)

        piped = make_pipelined_serve(
            mesh,
            stage_fn,
            params_spec=jax.tree.map(lambda _: P(PIPE_AXIS), params["layers"]),
            cache_spec=(
                P(PIPE_AXIS, DATA_AXIS),
                P(PIPE_AXIS, DATA_AXIS),
            ),
            row_specs={k: P(DATA_AXIS) for k in row},
        )
        x, (k_new, v_new) = piped(
            params["layers"], (cache["k"], cache["v"]), x, row
        )
    elif num_layers is not None and num_layers < cfg.num_hidden_layers:
        n = num_layers
        x, (k_upd, v_upd) = lax.scan(
            scan_body, x,
            (jax.tree.map(lambda a: a[:n], params["layers"]),
             cache["k"][:n], cache["v"][:n]),
        )
        k_new = jnp.concatenate([k_upd, cache["k"][n:]], axis=0)
        v_new = jnp.concatenate([v_upd, cache["v"][n:]], axis=0)
    else:
        x, (k_new, v_new) = lax.scan(
            scan_body, x, (params["layers"], cache["k"], cache["v"])
        )
    logits = _head_logits(cfg, params, x, logits_idx, None, all_logits)
    new_cache = {"k": k_new, "v": v_new}
    if needs_pos_cache(cfg):
        new_cache["pos"] = pos_cache
    return logits, new_cache


def commit_kv(cache, src, dst):
    """Move accepted speculative K/V lines ``src`` (R, K) into their
    committed positions ``dst`` (R, K) — the TPU-native version of the
    reference's token-commit copy kernels (reference
    ``request_manager.cu`` commit_tokens + the KV-cache commit in
    ``tree_inc_multihead_self_attention.cu``). Unused slots should map
    scratch→scratch. Functional gather-then-scatter, so overlapping
    src/dst ranges are safe. The (R, S1) position buffer of ALiBi /
    sliding-window caches moves with the lines."""
    R = src.shape[0]
    bidx = jnp.arange(R)[:, None]
    out = {}
    for name, buf in cache.items():
        if name == "pos":  # (R, S1)
            out[name] = buf.at[bidx, dst].set(buf[bidx, src])
        else:  # (L, R, S1, KV, dk)
            out[name] = buf.at[:, bidx, dst].set(buf[:, bidx, src])
    return out


def reorder_slots(
    cache: Dict[str, jnp.ndarray], src: jnp.ndarray  # (R,) int32
) -> Dict[str, jnp.ndarray]:
    """Gather cache slots: new slot r takes slot src[r]'s lines — beam
    search reorders hypotheses across request slots this way (the
    reference's beam attention forks sub-request KV instead,
    spec_inc_multihead_self_attention.cu). The position buffer's slot
    dim leads instead of following the layer dim."""
    return {
        name: (buf[src] if name == "pos" else buf[:, src])
        for name, buf in cache.items()
    }


# ---------------------------------------------------------------------------
# Paged serving path (Ragged Paged Attention layout, PAPERS.md arxiv
# 2604.15464): K/V live in a pool of fixed-size token pages shared by all
# request slots; each slot's page table maps logical cache lines
# (line // page_size) to physical pages. HBM is proportional to pages
# allocated — live tokens — instead of slots × max_len, which is what
# lets one chip serve the reference's 64 request slots. The XLA path
# gathers the virtual cache through the table with ``jnp.take`` and runs
# the dense _serve_attend math (bit-for-bit parity with the dense
# layout); ``kernels="pallas"`` routes through the fused ragged paged
# kernel (serve/kernels.py), which DMAs pages directly. The extra
# per-line position buffer (ALiBi/sliding-window families) pages the
# same way.

#: decode-step fusions the generic decoder's serving step supports
#: (ServingConfig.fused_decode; the engine validates requests against
#: this). "rope_kv_write": serve_step_paged folds RoPE (or, for
#: learned-position families, just the quantizing KV page write) into
#: the ragged paged Pallas kernel; ALiBi batches keep the unfused
#: path at run time because the additive bias already excludes the
#: Pallas kernel. (The sampling head is no fusion: the engine's step
#: program holds the one its batch needs.)
FUSED_DECODE = ("rope_kv_write",)

#: serve_step_paged takes a PACKED token axis (its ``pack``): the engine
#: may run a mixed step's norms, projections, FFN and residual stream
#: over the tokens that exist, at one of a few compiled widths
#: (serve/engine.pack_widths), and keep (slots, chunk) for attention
#: alone. A family whose step has no such axis leaves this unset and is
#: served the padded program.
PACKED_STEP = True


def init_paged_kv_cache(
    cfg: DecoderConfig, num_pages: int, page_size: int, dtype=None,
    kv_quant: Optional[str] = None, extra_rows: int = 0,
):
    """Pool (L, num_pages+1, page_size, KV, dk); pool row ``num_pages``
    is the shared scratch page — unallocated page-table entries point
    there, so padding writes and gathers through unallocated entries
    never touch live pages (the paged analog of the dense layout's
    per-slot scratch row). ALiBi/sliding-window configs also page the
    per-line position buffer. With ``kv_quant`` the pools store
    quantized codes — int8, or packed int4 nibbles (two codes per byte
    along dk, trailing dim ``head_dim // 2``) — plus per-page-per-KV-
    head f32 ``k_scale``/``v_scale`` rows, zero-initialised: a zero
    scale marks a page with no committed lines (serve/kv_quant.py; the
    position buffer stays int32 — it is exact metadata, not tensor
    payload). ``extra_rows`` appends never-referenced pad rows AFTER
    the scratch row: context-parallel serving
    (ServingConfig.kv_shard="context") shards pool rows over the mesh
    ``seq`` axis and pads the row count to a multiple of the shard
    degree; no table entry ever points past the scratch row, so the
    pads are pure alignment."""
    L, KV, dk = cfg.num_hidden_layers, cfg.num_key_value_heads, cfg.head_dim
    dt = dtype or cfg.dtype
    spec = None
    if kv_quant is not None:
        from ..serve.kv_quant import resolve_spec

        spec = resolve_spec(kv_quant)
        dt = spec.dtype
        if dk % spec.pack:
            raise ValueError(
                f"kv_quant={kv_quant!r} packs {spec.pack} codes per "
                f"element along head_dim, which needs head_dim "
                f"({dk}) divisible by {spec.pack}"
            )
        dk = dk // spec.pack
    rows = num_pages + 1 + int(extra_rows)
    shape = (L, rows, page_size, KV, dk)
    cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if spec is not None:
        sshape = (L, rows, KV)
        cache["k_scale"] = jnp.zeros(sshape, jnp.float32)
        cache["v_scale"] = jnp.zeros(sshape, jnp.float32)
    if needs_pos_cache(cfg):
        cache["pos"] = jnp.zeros((rows, page_size), jnp.int32)
    return cache


def paged_kv_cache_pspecs(cfg: DecoderConfig = None, *, pipeline: bool = False,
                          kv_quant: Optional[str] = None,
                          kv_shard: Optional[str] = None):
    """Pages shard over DP, KV heads over TP (MQA replicates, as in the
    dense layout); quantized scale rows shard like their pools (pages
    on data, KV heads on model). ``kv_shard="context"`` shards pool
    rows (and the position buffer's) over the SEQ axis instead: each
    sequence shard holds its own slice of one request's pages, which
    ring ragged paged attention reads locally
    (serve/kernels.ring_ragged_paged_attention)."""
    kv_axis = (
        None if (cfg is not None and cfg.num_key_value_heads == 1)
        else MODEL_AXIS
    )
    page_axis = SEQ_AXIS if kv_shard == "context" else DATA_AXIS
    pp = PIPE_AXIS if pipeline else None
    specs = {
        "k": P(pp, page_axis, None, kv_axis, None),
        "v": P(pp, page_axis, None, kv_axis, None),
    }
    if kv_quant is not None:
        specs["k_scale"] = P(pp, page_axis, kv_axis)
        specs["v_scale"] = P(pp, page_axis, kv_axis)
    if cfg is not None and needs_pos_cache(cfg):
        specs["pos"] = P(page_axis, None)
    return specs


def _page_lookup(page_table, cache_positions, page_size):
    logical = cache_positions // page_size
    phys = jnp.take_along_axis(page_table, logical, axis=1)
    return phys, cache_positions % page_size


def _layer_of(a, layer):
    """Layer ``layer`` of a stacked (L, ...) pool or scale array: a
    read-only slice, which the compiler fuses into the gather that reads
    it. Per-layer operands (``layer`` None) pass through."""
    if layer is None or a is None:
        return a
    return lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)


@sublayer("attn.write")
def _write_kv_lines(k_pool, v_pool, k_scale, v_scale, layer, phys, off,
                    k, v, qmax):
    """Commit the step's new K/V lines at their table-resolved (page,
    offset) — of the per-layer pools, or with ``layer`` of that layer's
    pages inside the stacked pools, in place (R*C lines, no layer
    sliced out or put back). Quantizing when ``qmax`` is set
    (serve/kv_quant.py). Returns the four arrays."""
    if qmax is not None:
        from ..serve.kv_quant import quant_line_write

        k_pool, k_scale = quant_line_write(k_pool, k_scale, phys, off, k,
                                           qmax, layer)
        v_pool, v_scale = quant_line_write(v_pool, v_scale, phys, off, v,
                                           qmax, layer)
        return k_pool, v_pool, k_scale, v_scale
    at = (phys, off) if layer is None else (layer, phys, off)
    k_pool = k_pool.at[at].set(k.astype(k_pool.dtype))
    v_pool = v_pool.at[at].set(v.astype(v_pool.dtype))
    return k_pool, v_pool, k_scale, v_scale


def _pallas_pools(k_pool, v_pool, k_scale, v_scale, layer):
    """The pools as a Pallas paged kernel takes them: ``(k, v,
    keywords)``. With ``layer`` each stack goes as its free
    (L*(P+1), ...) view, in which layer l's pages are rows ``l*(P+1)``
    on, and the keywords carry that row offset for the kernel's page
    index maps."""
    if layer is None:
        return k_pool, v_pool, dict(k_scale=k_scale, v_scale=v_scale)
    k, v, ks, vs = (
        a if a is None else a.reshape((-1,) + a.shape[2:])
        for a in (k_pool, v_pool, k_scale, v_scale)
    )
    return k, v, dict(k_scale=ks, v_scale=vs,
                      row_offset=layer * k_pool.shape[1])


def _pack_tokens(tokens, positions, q_len, page_table, page_size, cache_len,
                 width):
    """The packed token axis of a (R, C) step at the static ``width``:
    the real tokens (a row's leading ``q_len`` columns,
    serve/kernels.real_query_lengths) in row-major order, derived on the
    device. Returns ``(tokens, positions, phys, off)``, each (1, width)
    — spare places hold token 0 at the scratch position, whose K/V line
    is the scratch line of row 0 — and ``(place, flat)``: ``place``
    (R, C) the packed place of every column (padding columns point at
    some real place: attention never reads them as queries that count),
    ``flat`` (width,) the r*C+c each packed place came from. The caller
    has chosen ``width`` to hold the real tokens
    (serve/engine.run_mixed)."""
    R, C = positions.shape
    real = (jnp.arange(C, dtype=jnp.int32)[None] < q_len[:, None]).reshape(-1)
    (flat,) = jnp.nonzero(real, size=width, fill_value=R * C)
    spare = flat >= R * C
    flat = jnp.where(spare, 0, flat).astype(jnp.int32)
    place = jnp.clip(jnp.cumsum(real.astype(jnp.int32)) - 1, 0, width - 1)
    tok = jnp.where(spare, 0, tokens.reshape(-1)[flat])
    pos = jnp.where(spare, cache_len, positions.reshape(-1)[flat])
    phys = page_table[flat // C, pos // page_size]
    return ((tok[None], pos[None], phys[None], (pos % page_size)[None]),
            (place.reshape(R, C), flat))


def _spread_queries(q, pack):
    """Packed queries (1, T, H, dk) to the (R, C, H, dk) block the
    attention of a paged step takes; ``pack`` None: ``q`` is that
    block already."""
    if pack is None:
        return q
    return jnp.take(q[0], pack[0], axis=0, mode="clip")


def _gather_attended(attn, pack):
    """The attention result (R, C, ...) as (B, S, H*dk) on the token
    axis of the block's residual stream: (R, C) itself, or with
    ``pack`` the packed axis (1, T)."""
    R, C = attn.shape[:2]
    if pack is None:
        return attn.reshape(R, C, -1)
    return jnp.take(attn.reshape(R * C, -1), pack[1], axis=0, mode="clip")[None]


def _add_attn_ffn(cfg: DecoderConfig, p, x, h, attn, routed, layer,
                  kernels: str):
    """The second half of a paged block: the attention result and the
    FFN added to the residual stream ``x`` (``h``: the block's first
    norm, which a parallel block feeds to both). ``routed`` None: the
    FFN is :func:`_ffn`, -> (x,). Else ``(real, counts)``
    (:func:`serve_step_paged`): a sparse layer routes its real tokens
    (:func:`_routed_ffn`) and writes its tokens per expert into row
    ``layer`` of ``counts``, -> (x, counts)."""
    if cfg.parallel_block:
        if cfg.parallel_two_norms:
            h2 = _norm(cfg, x, p["mlp_norm_scale"], p.get("mlp_norm_bias"))
        else:
            h2 = h
        x = x + attn
    else:
        x = x + attn
        h2 = _norm(cfg, x, p["mlp_norm_scale"], p.get("mlp_norm_bias"))
    if routed is None:
        return (x + _ffn(cfg, p, h2),)
    real, counts = routed
    out, given = _routed_ffn(cfg, p, h2, real, layer, kernels)
    return x + out, lax.dynamic_update_index_in_dim(counts, given, layer, 0)


def serve_block_paged(cfg, p, x, rope, bias, mask, k_pool, v_pool,
                      phys, off, page_table, kernels: str = "xla",
                      k_scale=None, v_scale=None, qmax=None,
                      *, fused_rope: bool = False, logical=None,
                      cp_mesh=None, layer=None, q_len=None, work=None,
                      pack=None, routed=None):
    """Paged twin of :func:`serve_block`: scatter new K/V at the
    table-resolved (page, offset); attend over the virtual cache read
    through the table (``jnp.take`` gather, or the fused ragged paged
    kernel when ``kernels='pallas'`` and no additive bias is in play).
    With ``qmax`` the pool is quantized (serve/kv_quant.py): the commit
    quantizes in-step and reads dequantize at the page scales (fused
    in-kernel on the Pallas path). Returns
    ``(x, k_pool, v_pool, k_scale, v_scale)``.

    ``layer`` (the layer loop of :func:`serve_step_paged`): the pools
    and scales are every layer's, stacked (L, P+1, ...), and this block
    addresses its own pages inside them, returning the stacks updated in
    place. The XLA path writes at ``[layer, page, offset]`` and reads a
    read-only slice; the Pallas kernels take the (L*(P+1), ...) view and
    the row offset ``layer*(P+1)`` in their page index maps; the ring
    path, whose pool rows are sharded, takes its layer out and puts it
    back (``dynamic_update_slice``: one layer moved, never the stack).

    ``fused_rope`` (megakernel decode step): on the Pallas path RoPE —
    or, for non-RoPE position schemes, just the quantizing KV commit —
    moves inside the ragged paged kernel
    (serve/kernels.fused_rope_paged_attention). ALiBi batches keep the
    unfused path (the additive bias already excludes the Pallas
    kernel); on kernels="xla" the flag is a no-op — the unfused XLA
    step is the CPU-parity fallback. On a sequence-sharded mesh
    (``cp_mesh``) the fused prologue joins the RING body instead
    (PR-11's exclusion, lifted — serve/kernels.
    ring_ragged_paged_attention's ``fused`` mode).

    ``q_len`` (R,): the real queries of each row
    (serve/kernels.real_query_lengths), for the plain Pallas kernel,
    which then computes for those alone; the other paths take every
    column. ``work`` (serve/kernels.step_work): the table entries that
    kernel's grid runs, made once a step.

    ``pack`` (:func:`_pack_tokens`; the unfused paths without a ring):
    ``x``, ``rope``, ``phys`` and ``off`` are on the packed token axis
    (1, T) and so is everything here that is not attention; the queries
    are spread to (R, C) for the attention call alone — its mask, bias,
    ``q_len`` and result shape are the padded step's — and its result
    is gathered back.

    ``routed`` (:func:`_add_attn_ffn`; with ``layer``, off the ring): a
    sparse layer routes its real tokens, ``p``'s ``EXPERT_STACKS`` are
    every layer's, and the step's tokens per expert are returned as a
    sixth value."""
    from ..serve import kernels as _pk

    if cp_mesh is None and not (kernels == "pallas" and bias is None):
        # the unfused XLA path — the CPU-parity reference every fusion
        # anchors on
        return _block_paged_xla(
            cfg, p, x, rope, bias, mask, k_pool, v_pool, phys, off,
            page_table, k_scale, v_scale, qmax, layer=layer, pack=pack,
            routed=routed,
        )
    if cp_mesh is not None and layer is not None:
        # the ring's pool rows are sharded over ``seq``, which the rows
        # view would break: serve the layer as a per-layer pool and put
        # it back into the stack in place
        pools = (k_pool, v_pool, k_scale, v_scale)
        x, *new = serve_block_paged(
            cfg, p, x, rope, bias, mask,
            *(_layer_of(a, layer) for a in pools[:2]), phys, off,
            page_table, kernels, *(_layer_of(a, layer) for a in pools[2:]),
            qmax, fused_rope=fused_rope, logical=logical, cp_mesh=cp_mesh,
        )
        return (x, *(
            a if a is None
            else lax.dynamic_update_index_in_dim(a, b, layer, 0)
            for a, b in zip(pools, new)
        ))
    h = _norm(cfg, x, p["attn_norm_scale"], p.get("attn_norm_bias"))
    cos, sin = rope if rope is not None else (None, None)
    fused = fused_rope and kernels == "pallas" and bias is None
    # a fused prologue does RoPE and the line commit inside the kernel
    q, k, v = _project_rope(cfg, p, h, None if fused else rope)
    # the attention call; the line write inside it keeps its own scope
    with sublayer("attn.core"):
        if fused and cp_mesh is not None:
            # ring fused prologue: RoPE + the resident-line commit move
            # inside the per-shard shard_map body (full-precision pools;
            # quantized raises loudly in the kernel and is excluded at
            # ServingConfig validation)
            attn, k_pool, v_pool = _pk.ring_ragged_paged_attention(
                q, k_pool, v_pool, page_table, mask, cp_mesh,
                fused=dict(k_new=k, v_new=v, cos=cos, sin=sin,
                           phys=phys, off=off),
            )
        elif fused:
            k_rows, v_rows, kw = _pallas_pools(k_pool, v_pool, k_scale,
                                               v_scale, layer)
            attn, *new = _pk.fused_rope_paged_attention(
                q, k, v, cos, sin, k_rows, v_rows, page_table, logical, off,
                mask, qmax=qmax, **kw,
            )
            k_pool, v_pool, k_scale, v_scale = (
                b if b is None else b.reshape(a.shape)
                for a, b in zip((k_pool, v_pool, k_scale, v_scale), new)
            )
        else:
            k_pool, v_pool, k_scale, v_scale = _write_kv_lines(
                k_pool, v_pool, k_scale, v_scale, layer, phys, off, k, v, qmax
            )
            if cp_mesh is not None:
                if bias is not None:
                    # ALiBi's additive bias needs per-key-position terms the
                    # ring program does not carry yet (same exclusion as the
                    # Pallas kernel); sliding-window masks are fine — they
                    # are mask refinements, already folded in before this
                    # call.
                    raise NotImplementedError(
                        "ring context parallelism is not composed with ALiBi "
                        "position bias — serve this family with "
                        "kv_shard='context' on a seq-degree-1 mesh (the table-"
                        "gather layout), or use a RoPE/learned-position family"
                    )
                attn = _pk.ring_ragged_paged_attention(
                    q, k_pool, v_pool, page_table, mask, cp_mesh,
                    k_scale=k_scale, v_scale=v_scale,
                )
            else:  # kernels == "pallas", bias None (xla returned above)
                k_rows, v_rows, kw = _pallas_pools(k_pool, v_pool, k_scale,
                                                   v_scale, layer)
                attn = _pk.ragged_paged_attention(
                    _spread_queries(q, pack), k_rows, v_rows, page_table,
                    mask, q_len=q_len, work=work, **kw
                )
        attn = _gather_attended(attn, pack)
    attn = _project_out(cfg, p, attn)
    x, *counts = _add_attn_ffn(cfg, p, x, h, attn, routed, layer, kernels)
    return (x, k_pool, v_pool, k_scale, v_scale, *counts)


def _block_paged_xla(cfg: DecoderConfig, p, x, rope, bias, mask,
                     k_pool, v_pool, phys, off, page_table,
                     k_scale=None, v_scale=None, qmax=None, layer=None,
                     pack=None, routed=None):
    """One block of the UNFUSED XLA paged step: the body of
    :func:`serve_block_paged`'s XLA path. ``layer``: the pools are the
    stacked ones, ``pack``: the token axis is packed, ``routed``: a
    sparse layer routes its tokens, through ``lax.ragged_dot`` (see
    :func:`serve_block_paged`)."""
    from ..serve import kernels as _pk

    h = _norm(cfg, x, p["attn_norm_scale"], p.get("attn_norm_bias"))
    q, k, v = _project_rope(cfg, p, h, rope)
    k_pool, v_pool, k_scale, v_scale = _write_kv_lines(
        k_pool, v_pool, k_scale, v_scale, layer, phys, off, k, v, qmax
    )
    with sublayer("attn.core"):
        k_l, v_l = _layer_of(k_pool, layer), _layer_of(v_pool, layer)
        if qmax is not None:
            k_virt = _pk.dequant_pages(
                k_l, _layer_of(k_scale, layer), page_table, q.dtype
            )
            v_virt = _pk.dequant_pages(
                v_l, _layer_of(v_scale, layer), page_table, q.dtype
            )
        else:
            k_virt = _pk.gather_pages(k_l, page_table)
            v_virt = _pk.gather_pages(v_l, page_table)
        attn = _serve_attend(cfg, _spread_queries(q, pack), k_virt, v_virt,
                             bias, mask)
        attn = _gather_attended(attn, pack)
    attn = _project_out(cfg, p, attn)
    x, *counts = _add_attn_ffn(cfg, p, x, h, attn, routed, layer, "xla")
    return (x, k_pool, v_pool, k_scale, v_scale, *counts)


def _paged_serve_context(cfg, cache, positions, cache_positions, mask,
                         page_table, cache_len):
    """Shared prologue of the paged step/debug paths: page lookup, the
    causal-or-padded mask over the virtual cache, and the paged position
    buffer + ALiBi bias/sliding-window refinement."""
    from ..serve.kernels import gather_pages, paged_serve_mask

    ps = cache["k"].shape[2]
    phys, off = _page_lookup(page_table, cache_positions, ps)
    mask = paged_serve_mask(mask, positions, page_table.shape[1], ps, cache_len)

    bias = None
    pos_pool = None
    if needs_pos_cache(cfg):
        pos_pool = cache["pos"].at[phys, off].set(positions.astype(jnp.int32))
        pos_virt = gather_pages(pos_pool, page_table)  # (R, S_virt)
        if cfg.positions == "alibi":
            slopes = alibi_slopes(cfg.num_attention_heads)
            dist = (
                positions.astype(jnp.float32)[:, None, :, None]
                - pos_virt.astype(jnp.float32)[:, None, None, :]
            )
            bias = -slopes[None, :, None, None] * dist
        if cfg.sliding_window:
            mask = mask & (
                pos_virt[:, None, :]
                > positions[:, :, None] - cfg.sliding_window
            )
    return phys, off, mask, bias, pos_pool


@sublayer("glue")
def serve_step_paged(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,      # (R, C)
    positions: jnp.ndarray,   # (R, C)
    logits_idx: jnp.ndarray,  # (R,)
    mask: Optional[jnp.ndarray],   # (R, C, cache_len+1) bool or None
    cache_positions: Optional[jnp.ndarray],
    page_table: jnp.ndarray,  # (R, NP) int32
    *,
    cfg: DecoderConfig,
    cache_len: int,
    all_logits: bool = False,
    kernels: str = "xla",
    kv_quant: Optional[str] = None,
    fused_rope: bool = False,
    num_layers: Optional[int] = None,
    mesh=None,
    cp_mesh=None,
    pack: Optional[int] = None,
):
    """Paged twin of :func:`serve_step` — same contract plus the
    per-slot page table; prefill chunks, single-token decode and
    tree-verify all read/write K/V through the table. ``kv_quant``
    selects the quantized pool layout (serve/kv_quant.py): the KV commit
    quantizes in-step and attention dequantizes at read time.
    ``fused_rope`` (megakernel decode step) folds RoPE and the KV page
    write into the Pallas kernel per block — a no-op on the XLA path,
    which already is the fused variants' CPU-parity reference.
    ``num_layers`` is the layer-sliced early-exit draft step
    (:func:`serve_step`): deeper pool rows (and their quant scale rows)
    pass through untouched for the verify pass to own. ``cp_mesh``
    (context parallelism, ServingConfig.kv_shard="context" on a
    sequence-sharded mesh) routes every block's attention through ring
    ragged paged attention over the seq-sharded pool — ALiBi-bias
    families reject it, see serve_block_paged.

    ``pack`` (a static width T that holds the step's real tokens; the
    engine picks it from the positions, serve/engine.run_mixed): embed,
    norms, projections, FFN, residual stream, K/V line writes and the
    final norm run over a packed (1, T) token axis, attention alone at
    (R, C) (:func:`_pack_tokens`, :func:`serve_block_paged`), and each
    row's logits are taken at the packed place of its ``logits_idx``.
    Every real token goes through the operations of the padded step;
    positions that held none stop being computed. None: the padded
    step, operation for operation.

    A sparse model on one device, at a width whose pairs are 16 an
    expert (:func:`routes_tokens`: the mixed step's rungs, not the
    C=1 step of a few slots), sends the (token, expert) pairs of its
    REAL tokens through the grouped expert matmuls
    (:func:`routed_experts_ffn`; its experts' weights stay stacked over
    the layers, each layer addressing its own). The returned cache of a
    sparse model also holds ``moe_counts`` (:func:`step_counts`: an
    output, not an input)."""
    if mesh is not None and mesh.shape.get(PIPE_AXIS, 1) > 1:
        raise NotImplementedError(
            "paged KV serving is not composed with pipeline parallelism "
            "yet — use kv_layout='dense' with pipe>1"
        )
    if pack is not None and (
        mask is not None or cache_positions is not None or all_logits
        or fused_rope or cp_mesh is not None
    ):
        raise ValueError(
            "a packed token axis serves the causal mixed step alone: no "
            "explicit mask or cache positions, no all_logits, no fused "
            "RoPE prologue, no ring"
        )
    # the causal mask built below follows from the positions, and so do
    # the real queries of a row and the pages they may see (the Pallas
    # kernel's grid; a window's lower edge only where a line's place is
    # its position); an explicit mask (a token tree) already leaves its
    # padding columns empty, and every column and table entry counts
    q_len = work = None
    if mask is None:
        from ..serve.kernels import real_query_lengths, step_work

        q_len = real_query_lengths(positions, cache_len)
        if kernels == "pallas":
            window = cfg.sliding_window if cache_positions is None else 0
            work = step_work(positions, q_len, cache["k"].shape[2],
                             page_table.shape[1], window)
    if cache_positions is None:
        cache_positions = positions
    pack_idx, token_axis = None, (tokens, positions)
    if pack is not None:
        packed, pack_idx = _pack_tokens(
            tokens, positions, q_len, page_table, cache["k"].shape[2],
            cache_len, pack,
        )
        token_axis, lines = packed[:2], packed[2:]
    x = _embed_in(cfg, params, *token_axis)
    rope = _rope_of(cfg, token_axis[1])
    # the mask, the bias and the position pool follow from the (R, C)
    # positions whatever the token axis: attention stays (R, C)
    phys, off, mask, bias, pos_pool = _paged_serve_context(
        cfg, cache, positions, cache_positions, mask, page_table, cache_len
    )
    if pack is not None:  # the K/V lines written are the packed tokens'
        phys, off = lines
    logical = cache_positions // cache["k"].shape[2]

    n = cfg.num_hidden_layers
    if num_layers is not None:
        n = min(num_layers, n)
    qmax, scales = None, (None, None)
    if kv_quant is not None:
        from ..serve.kv_quant import resolve_spec

        qmax = resolve_spec(kv_quant).qmax
        scales = (cache["k_scale"], cache["v_scale"])

    # a sparse layer's real tokens, on the block's token axis: a packed
    # place below the step's real count, a row's leading ``q_len``
    # columns, every column under an explicit mask
    stacks, real = (), None
    if routes_tokens(cfg, params["layers"], pack or tokens.size):
        stacks = EXPERT_STACKS
        if pack is not None:
            real = jnp.arange(pack, dtype=jnp.int32) < q_len.sum()
        elif q_len is not None:
            real = (jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
                    < q_len[:, None]).reshape(-1)
        else:
            real = jnp.ones((tokens.size,), jnp.bool_)
    counts = {name: jnp.zeros(shape, jnp.int32)
              for name, shape in step_counts(cfg).items()}
    layers = params["layers"]

    # The stacked pools are the loop's CARRY, updated in place: a layer
    # addresses its own pages inside them (serve_block_paged, ``layer``).
    # Scanned in as xs and out as ys they are two buffers: a copy of the
    # whole pool a step, a slice out and a write-back of every layer
    # (tests/test_chip_compile.py holds the compiled step to this). A
    # routed layer addresses its experts inside their stacks likewise (a
    # layer's experts sliced out to feed a kernel call would be a copy
    # of them a step) and its row of the tokens per expert in the carry.
    def scan_body(carry, l):
        h, kc, vc, ks, vs, *given = carry
        p_l = jax.tree.map(
            lambda a: _layer_of(a, l),
            {k: w for k, w in layers.items() if k not in stacks})
        p_l.update((k, layers[k]) for k in stacks)
        return serve_block_paged(
            cfg, p_l, h, rope, bias, mask, kc, vc, phys, off,
            page_table, kernels, ks, vs, qmax,
            fused_rope=fused_rope, logical=logical, cp_mesh=cp_mesh,
            layer=l, q_len=q_len, work=work, pack=pack_idx,
            routed=(real, *given) if given else None,
        ), None

    carry = (x, cache["k"], cache["v"], *scales)
    if real is not None:
        carry += (counts["moe_counts"],)
    (x, k_new, v_new, *scales), _ = lax.scan(scan_body, carry, jnp.arange(n))
    if real is not None:
        counts["moe_counts"] = scales.pop()
    new_cache = {"k": k_new, "v": v_new, **counts}
    if qmax is not None:
        new_cache["k_scale"], new_cache["v_scale"] = scales
    logits = _head_logits(cfg, params, x, logits_idx, pack_idx, all_logits)
    if needs_pos_cache(cfg):
        new_cache["pos"] = pos_pool
    return logits, new_cache


def copy_page_kv(cache, src, dst):
    """Copy one physical page's lines (all layers) to another page — the
    device half of prefix-cache copy-on-write (serve/prefix_cache.py): a
    request appending into a shared cached tail page writes into a
    private copy, never the cached original. The position pool pages
    like K/V but without the layer dim. Dtype-agnostic: quantized
    pools' int8 codes and their (L, P+1, KV) scale rows copy through
    the same pool-row scatter, so a COW'd page dequantizes identically
    to its original."""
    out = {}
    for name, buf in cache.items():
        if name == "pos":  # (P+1, ps)
            out[name] = buf.at[dst].set(buf[src])
        else:              # (L, P+1, ps|KV, ...)
            out[name] = buf.at[:, dst].set(buf[:, src])
    return out


def gather_page_kv(cache, page):
    """Slice one physical page's content out of every cache buffer — the
    device half of a hierarchical-KV SPILL (serve/prefix_cache.py host
    tier): the engine starts an async device→host copy on the returned
    pytree and the page returns to the free list. Covers K/V pools AND
    the quantized layout's per-page scale rows, so a spilled page
    re-admits byte-for-byte; the position pool pages like K/V but
    without the layer dim."""
    out = {}
    for name, buf in cache.items():
        if name == "pos":  # (P+1, ps)
            out[name] = buf[page]
        else:              # (L, P+1, ps|KV, ...)
            out[name] = buf[:, page]
    return out


def scatter_page_kv(cache, page, values):
    """Write a previously spilled page's content (the pytree
    :func:`gather_page_kv` produced) into pool row ``page`` — the device
    half of a host-tier RE-ADMIT. Exact inverse of the gather: codes and
    scales land byte-for-byte, which is what keeps
    spilled-then-readmitted generation bitwise identical to the
    never-evicted warm path."""
    out = {}
    for name, buf in cache.items():
        if name == "pos":
            out[name] = buf.at[page].set(values[name])
        else:
            out[name] = buf.at[:, page].set(values[name])
    return out


def commit_kv_paged(cache, page_table, src, dst, *, kv_quant=None):
    """:func:`commit_kv` through the page table: accepted speculative
    lines move between table-resolved (page, offset) pairs. Functional
    gather-then-scatter, so overlapping ranges stay safe; scratch→
    scratch no-ops are harmless duplicates (identical values). The
    position pool pages like K/V but without the layer dim.

    On a quantized pool the codes cannot move verbatim (source and
    destination pages carry different scales): the lines dequantize at
    their source page's scale and re-commit through the standard
    quantized write (serve/kv_quant.quant_commit_lines), updating the
    destination pages' amax scales exactly as a fresh write would (the
    position buffer still moves verbatim — it is exact int32
    metadata)."""
    ps = cache["k"].shape[2]
    s_phys, s_off = _page_lookup(page_table, src, ps)
    d_phys, d_off = _page_lookup(page_table, dst, ps)
    if kv_quant is not None:
        from ..serve.kv_quant import quant_commit_lines, resolve_spec

        qmax = resolve_spec(kv_quant).qmax
        out = dict(cache)
        for name in ("k", "v"):
            out[name], out[name + "_scale"] = quant_commit_lines(
                cache[name], cache[name + "_scale"],
                s_phys, s_off, d_phys, d_off, qmax,
            )
        if "pos" in cache:
            out["pos"] = cache["pos"].at[d_phys, d_off].set(
                cache["pos"][s_phys, s_off]
            )
        return out
    out = {}
    for name, buf in cache.items():
        if name == "pos":  # (P+1, ps)
            out[name] = buf.at[d_phys, d_off].set(buf[s_phys, s_off])
        else:              # (L, P+1, ps, KV, dk)
            out[name] = buf.at[:, d_phys, d_off].set(buf[:, s_phys, s_off])
    return out


def reorder_slots_paged(cache, page_table, src):
    """:func:`reorder_slots` for the paged layout: page OWNERSHIP stays
    with each slot (the host table is untouched) and page CONTENT is
    copied — new slot r's pages receive slot src[r]'s lines. Requires
    the destination slots to have (at least) the source slots' pages
    allocated, which beam search guarantees by construction
    (equal-length hypotheses)."""
    src_pages = page_table[src].reshape(-1)
    dst_pages = page_table.reshape(-1)
    out = {}
    for name, buf in cache.items():
        if name == "pos":
            out[name] = buf.at[dst_pages].set(buf[src_pages])
        else:
            out[name] = buf.at[:, dst_pages].set(buf[:, src_pages])
    return out


def serve_debug_activations(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    cache_positions: Optional[jnp.ndarray] = None,
    *,
    cfg: DecoderConfig,
    kernels: str = "xla",
    page_table: Optional[jnp.ndarray] = None,
    cache_len: Optional[int] = None,
    kv_quant: Optional[str] = None,
):
    """Per-layer hidden-state capture for ``inference_debugging``
    (reference's per-op tensor dump mode, serve/__init__.py:48 — saving
    all inputs/outputs to file for serving triage). Eager Python loop so
    each layer's output survives as its own array; cache writes are
    computed and DISCARDED (the engine's donating step does the real
    commit). Deliberately slow — a triage tool, not a serving path.
    ``kernels`` is accepted for signature parity with the engine's call
    and ignored — the triage path is deliberately the plain XLA one."""
    del kernels  # triage runs the reference XLA math
    if cache_positions is None:
        cache_positions = positions
    x = _embed_in(cfg, params, tokens, positions)
    rope = rope_freqs(cfg, positions) if cfg.positions == "rope" else None
    acts = []
    if page_table is not None:  # paged layout
        phys, off, mask, bias, _ = _paged_serve_context(
            cfg, cache, positions, cache_positions, mask, page_table,
            cache_len,
        )
        qmax = None
        if kv_quant is not None:
            from ..serve.kv_quant import resolve_spec

            qmax = resolve_spec(kv_quant).qmax
        for l in range(cfg.num_hidden_layers):
            p_l = jax.tree.map(lambda a: a[l], params["layers"])
            x, *_ = serve_block_paged(
                cfg, p_l, x, rope, bias, mask,
                cache["k"][l], cache["v"][l], phys, off, page_table,
                "xla",
                cache["k_scale"][l] if qmax is not None else None,
                cache["v_scale"][l] if qmax is not None else None,
                qmax,
            )
            acts.append(x)
        return acts
    R = tokens.shape[0]
    S1 = cache["k"].shape[2]
    if mask is None:
        from ..serve.kernels import causal_serve_mask

        mask = causal_serve_mask(positions, S1)
    bias = None
    if needs_pos_cache(cfg):
        bidx = jnp.arange(R)[:, None]
        pos_cache = cache["pos"].at[bidx, cache_positions].set(
            positions.astype(jnp.int32)
        )
        if cfg.positions == "alibi":
            slopes = alibi_slopes(cfg.num_attention_heads)
            dist = (
                positions.astype(jnp.float32)[:, None, :, None]
                - pos_cache.astype(jnp.float32)[:, None, None, :]
            )
            bias = -slopes[None, :, None, None] * dist
        if cfg.sliding_window:
            mask = mask & (
                pos_cache[:, None, :]
                > positions[:, :, None] - cfg.sliding_window
            )
    for l in range(cfg.num_hidden_layers):
        p_l = jax.tree.map(lambda a: a[l], params["layers"])
        x, _, _ = serve_block(
            cfg, p_l, x, rope, bias, mask,
            cache["k"][l], cache["v"][l], cache_positions,
        )
        acts.append(x)
    return acts


# ---------------------------------------------------------------------------
# Layers of several kinds in one model: runs of kinds


def layer_runs(kinds):
    """A static layer order as RUNS of one kind. ``kinds``: one entry a
    layer, each a tuple of the names of the parameter groups the layer
    takes its weights from, in the order its blocks run (("conv",
    "sparse"): a conv mixer, then a sparse FFN). Every group's weights
    are stacked over the layers that use the group, in layer order.
    Returns [(kind, {group: index of the run's first layer within the
    group's stack}, number of layers)]."""
    runs, seen = [], {}
    for kind in kinds:
        kind = tuple(kind)
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, {g: seen.get(g, 0) for g in kind}, 1])
        for g in kind:
            seen[g] = seen.get(g, 0) + 1
    return [tuple(r) for r in runs]


def layer_weights(stack, index, *, whole=()):
    """Layer ``index`` of a group's stacked weights; the leaves named in
    ``whole`` stay the stack (what a kernel call addresses by index: a
    slice of them would be a copy)."""
    return {name: w if name in whole else _layer_of(w, index)
            for name, w in stack.items()}


def run_layers(kinds, blocks, params, x, carried):
    """The generic layer loop of a model whose layers are of several
    kinds (ROADMAP B8): walks :func:`layer_runs` of ``kinds``, each run
    one ``fori_loop`` over its groups' stacked weights. ``blocks``: group name -> ``fn(stack,
    index, x, carried) -> (x, carried)``, where ``stack`` is
    ``params[group]`` and ``index`` the layer's place in it: the block
    takes its own weights out (:func:`layer_weights`; a read-only slice
    fuses into the matmul that reads it) and addresses its own part of
    ``carried`` by the same index. ``carried`` is
    whatever the blocks keep IN PLACE from layer to layer (a K/V pool
    for attention layers, a per-slot state for recurrent or convolution
    layers, counters): a pytree that is the loop's carry through every
    run, never scanned in and out, so a block updates its own layer
    inside the whole stack and nothing is copied
    (tests/test_chip_compile.py)."""
    for kind, first, n in layer_runs(kinds):
        def body(i, carry, kind=kind, first=first):
            x, carried = carry
            for g in kind:
                x, carried = blocks[g](params[g], first[g] + i, x, carried)
            return x, carried

        # a run of one layer is a loop too: with a traced index a block
        # addresses its layer inside the carried stacks in place, with
        # a static one the compiler copies the stacks it writes to
        x, carried = lax.fori_loop(0, n, body, (x, carried))
    return x, carried


def num_params(cfg: DecoderConfig) -> int:
    shapes = init_shapes(cfg)
    return sum(
        int(math.prod(s.shape)) for s in jax.tree.leaves(shapes)
    )


def flops_per_token(cfg: DecoderConfig, seq_len: int) -> int:
    """Forward FLOPs/token ≈ 2*n_params + attention quadratic term."""
    return (2 * num_params(cfg)
            + 4 * cfg.num_hidden_layers * cfg.hidden_size * seq_len)

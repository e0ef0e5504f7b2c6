"""LLaMA model family — the flagship architecture.

TPU-native equivalent of the reference's LLaMA builder (reference
``inference/models/llama.cc:23-280`` and ``python/flexflow/serve/models/
llama.py``): embedding → N × [rms_norm → attention(QKV+RoPE+GQA) →
residual_rms_norm → SwiGLU FFN] → rms_norm → lm_head → decode head.

Design differences from the reference, chosen for TPU:
  * **Stacked layers + ``lax.scan``**: all N layers' weights live in one
    pytree with a leading layer dim. One compiled block serves every
    layer (fast compile), the layer dim shards over the ``pipe`` axis for
    pipeline parallelism, and ``jax.checkpoint`` remats per block.
  * **bf16 compute / f32 accumulate** on the MXU via
    ``preferred_element_type``.
  * Training (full causal, :func:`block`) and serving (KV-cache
    prefill/decode/verify, :func:`serve_block`) share the projection and
    FFN math; serving batch layout comes from flexflow_tpu/serve.
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

from ..core.mesh import DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS
from .transformer import seeded_normal


@dataclasses.dataclass(frozen=True)
class LLaMAConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 2048
    dtype: Any = jnp.bfloat16
    tie_word_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def llama_7b(cls, **kw):
        return cls(**kw)

    @classmethod
    def llama_160m(cls, **kw):
        """The reference's standard SSM speculator (JackFram/llama-160m)."""
        d = dict(
            hidden_size=768,
            intermediate_size=3072,
            num_hidden_layers=12,
            num_attention_heads=12,
            num_key_value_heads=12,
        )
        d.update(kw)
        return cls(**d)

    @classmethod
    def tiny(cls, **kw):
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
        return cls(**d)

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **kw) -> "LLaMAConfig":
        d = dict(
            vocab_size=hf.get("vocab_size", 32000),
            hidden_size=hf.get("hidden_size", 4096),
            intermediate_size=hf.get("intermediate_size", 11008),
            num_hidden_layers=hf.get("num_hidden_layers", 32),
            num_attention_heads=hf.get("num_attention_heads", 32),
            num_key_value_heads=hf.get(
                "num_key_value_heads", hf.get("num_attention_heads", 32)
            ),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            rope_theta=hf.get("rope_theta", 10000.0),
            max_position_embeddings=hf.get("max_position_embeddings", 2048),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
        )
        d.update(kw)
        return cls(**d)


# ---------------------------------------------------------------------------
# RoPE (HF rotate-half convention; reference supports native + HF variants,
# inc_multihead_self_attention.cu:487)


def rope_freqs(cfg: LLaMAConfig, positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions (...,) int32 → cos/sin (..., head_dim)."""
    half = cfg.head_dim // 2
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., half)
    angles = jnp.concatenate([angles, angles], axis=-1)  # (..., head_dim)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., heads, head_dim); cos/sin broadcast over the head axis."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos[..., None, :] + rotated * sin[..., None, :]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Parameters


def init_params(key, cfg: LLaMAConfig) -> Dict[str, Any]:
    L, D, F = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
    H, KV, dk = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    dt = cfg.dtype
    ks = jax.random.split(key, 8)

    def norm_init(std, k, shape):
        return seeded_normal(k, std, shape=shape, dtype=dt)

    std = 0.02
    params = {
        "embed": norm_init(std, ks[0], (cfg.vocab_size, D)),
        "layers": {
            "attn_norm": jnp.ones((L, D), dt),
            "wq": norm_init(std, ks[1], (L, D, H * dk)),
            "wk": norm_init(std, ks[2], (L, D, KV * dk)),
            "wv": norm_init(std, ks[3], (L, D, KV * dk)),
            "wo": norm_init(std / math.sqrt(2 * L), ks[4], (L, H * dk, D)),
            "ffn_norm": jnp.ones((L, D), dt),
            "w1": norm_init(std, ks[5], (L, D, F)),
            "w2": norm_init(std / math.sqrt(2 * L), ks[6], (L, F, D)),
            "w3": norm_init(std, ks[7], (L, D, F)),
        },
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm_init(std, jax.random.fold_in(key, 99), (D, cfg.vocab_size))
    return params


def param_pspecs(cfg: LLaMAConfig, *, pipeline: bool = False) -> Dict[str, Any]:
    """Megatron TP shardings (reference's hardcoded TP rewrite,
    model.cc:3239-3312): QKV/up column-parallel, O/down row-parallel on
    the ``model`` axis. With ``pipeline`` the stacked layer dim shards
    over ``pipe``."""
    pp = PIPE_AXIS if pipeline else None
    specs = {
        "embed": P(None, None),
        "layers": {
            "attn_norm": P(pp, None),
            "wq": P(pp, None, MODEL_AXIS),
            "wk": P(pp, None, MODEL_AXIS),
            "wv": P(pp, None, MODEL_AXIS),
            "wo": P(pp, MODEL_AXIS, None),
            "ffn_norm": P(pp, None),
            "w1": P(pp, None, MODEL_AXIS),
            "w2": P(pp, MODEL_AXIS, None),
            "w3": P(pp, None, MODEL_AXIS),
        },
        "final_norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, MODEL_AXIS)
    return specs


# ---------------------------------------------------------------------------
# Forward


def _rms(x, gamma, eps):
    xf = x.astype(jnp.float32)
    r = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return ((xf * r).astype(x.dtype)) * gamma


def _mm(x, w):
    if isinstance(w, dict):  # int8/int4 weight-only quantization
        from ..quantization import dequantize

        w = dequantize(w, x.dtype)
    return jnp.matmul(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def attention(
    cfg: LLaMAConfig,
    q: jnp.ndarray,  # (B, S, H, dk) — rope applied
    k: jnp.ndarray,  # (B, T, KV, dk)
    v: jnp.ndarray,  # (B, T, KV, dk)
    mask: Optional[jnp.ndarray],  # (B, S, T) or (S, T) bool, True = attend
) -> jnp.ndarray:
    H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
    if KV != H:  # GQA: repeat KV heads
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum(
        "bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(cfg.head_dim)
    if mask is not None:
        m = mask if mask.ndim == 3 else mask[None]
        scores = jnp.where(m[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def block(
    cfg: LLaMAConfig,
    p: Dict[str, jnp.ndarray],  # one layer's params (no L dim)
    x: jnp.ndarray,  # (B, S, D)
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    attn_fn=None,  # override for sequence-parallel attention
):
    """One transformer block, training path (full local-sequence
    attention). The serving path with KV cache is :func:`serve_block`.
    Returns (x_out, None) — the None slot keeps the scan-body signature
    stable across train/serve variants."""
    B, S, D = x.shape
    H, KV, dk = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    h = _rms(x, p["attn_norm"], cfg.rms_norm_eps)
    q = _mm(h, p["wq"]).reshape(B, S, H, dk)
    k = _mm(h, p["wk"]).reshape(B, S, KV, dk)
    v = _mm(h, p["wv"]).reshape(B, S, KV, dk)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    attn = (attn_fn or attention)(cfg, q, k, v, mask)

    x = x + _mm(attn.reshape(B, S, H * dk), p["wo"])
    h2 = _rms(x, p["ffn_norm"], cfg.rms_norm_eps)
    ffn = _mm(jax.nn.silu(_mm(h2, p["w1"])) * _mm(h2, p["w3"]), p["w2"])
    return x + ffn, None


def causal_mask(S: int) -> jnp.ndarray:
    return jnp.tril(jnp.ones((S, S), bool))


def make_flash_attention(block_q: int = 128, block_k: int = 128):
    """Causal flash-attention attn_fn (Pallas kernel with custom VJP,
    ops/flash_attention.py): scores stream through VMEM instead of
    materialising the (B, H, S, S) tensor the XLA path writes to HBM."""
    from ..ops.flash_attention import flash_attention

    def attn_fn(cfg, q, k, v, mask):
        # mask is None by construction (forward() skips building it when
        # an attn_fn is supplied); causality is computed in-kernel
        H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
        if KV != H:
            rep = H // KV
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k
        )

    return attn_fn


def make_sp_attention(mesh, impl: str = "ring"):
    """Build a sequence-parallel attention override for :func:`block`
    (ring ppermute or Ulysses all-to-all over the ``seq`` axis — the
    long-context capability the reference lacks, SURVEY.md §7 step 7)."""
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


def _remat_policy(name):
    """See :func:`flexflow_tpu.core.remat.resolve_remat_policy` (shared
    across model families and the fused graph-IR ops)."""
    from ..core.remat import resolve_remat_policy

    return resolve_remat_policy(name)


def forward(
    params: Dict[str, Any],
    tokens: jnp.ndarray,  # (B, S) int32
    cfg: LLaMAConfig,
    *,
    positions: Optional[jnp.ndarray] = None,
    remat: bool = False,
    remat_policy: Optional[str] = None,
    shard_activations: bool = False,
    attn_fn=None,
) -> jnp.ndarray:
    """Training/eval forward: full causal attention, returns logits
    (B, S, V). ``attn_fn`` overrides the attention computation (see
    :func:`make_sp_attention` for ring/Ulysses sequence parallelism)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
    cos, sin = rope_freqs(cfg, positions)
    # SP attention derives causality from global positions — never
    # materialise the S×S mask on the long-context path.
    mask = None if attn_fn is not None else causal_mask(S)

    def constrain(t):
        if shard_activations:
            return lax.with_sharding_constraint(
                t, P(DATA_AXIS, SEQ_AXIS, None)
            )
        return t

    x = constrain(x)

    blk = functools.partial(block, cfg, attn_fn=attn_fn)
    if remat:
        blk = jax.checkpoint(blk, policy=_remat_policy(remat_policy))

    def scan_body(carry, p_l):
        y, _ = blk(p_l, carry, cos, sin, mask)
        return constrain(y), None

    x, _ = lax.scan(scan_body, x, params["layers"])
    x = _rms(x, params["final_norm"], cfg.rms_norm_eps)
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    return jnp.matmul(x, head, preferred_element_type=jnp.float32)


def next_token_loss(params, tokens, cfg, **kw) -> jnp.ndarray:
    """Causal LM loss: predict tokens[:, 1:] from tokens[:, :-1]."""
    logits = forward(params, tokens[:, :-1], cfg, **kw)
    targets = tokens[:, 1:].astype(jnp.int32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def make_train_step(
    cfg: LLaMAConfig,
    mesh,
    optimizer,
    *,
    num_microbatches: int = 1,
    remat: bool = True,
    remat_policy: Optional[str] = None,  # None (full) | "dots"
    shard_activations: bool = True,
    attention: str = "xla",  # "xla" | "flash" (Pallas, ops/flash_attention)
):
    """Build (init_fn, step_fn) jitted over ``mesh`` with the full
    dp/tp/pp/sp sharding stack.

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

        flash = attention == "flash"
        blk = functools.partial(
            block, cfg, attn_fn=make_flash_attention() if flash else None
        )
        if remat:
            blk = jax.checkpoint(blk, policy=_remat_policy(remat_policy))

        def loss_fn(params, tokens):
            B, S = tokens.shape
            Sm = S - 1
            inp, targets = tokens[:, :-1], tokens[:, 1:].astype(jnp.int32)
            x = jnp.take(params["embed"], inp.astype(jnp.int32), axis=0)
            if shard_activations and mesh.shape[SEQ_AXIS] > 1:
                x = lax.with_sharding_constraint(x, P(DATA_AXIS, SEQ_AXIS, None))
            cos, sin = rope_freqs(cfg, jnp.arange(Sm, dtype=jnp.int32))
            mask = None if flash else causal_mask(Sm)

            def block_stack(stage_layers, x_mb):
                def body(carry, p_l):
                    y, _ = blk(p_l, carry, cos, sin, mask)
                    return y, None

                y, _ = lax.scan(body, x_mb, stage_layers)
                return y

            mb = B // num_microbatches
            x_mb = x.reshape(num_microbatches, mb, Sm, cfg.hidden_size)
            piped = make_pipelined_apply(
                mesh,
                block_stack,
                num_microbatches=num_microbatches,
                params_spec=jax.tree.map(
                    lambda _: P(PIPE_AXIS), params["layers"]
                ),
            )
            y = piped(params["layers"], x_mb).reshape(B, Sm, cfg.hidden_size)
            y = _rms(y, params["final_norm"], cfg.rms_norm_eps)
            head = (
                params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
            )
            logits = jnp.matmul(y, head, preferred_element_type=jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
            return nll.mean()

    def step_fn(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, loss

    data_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
    step = jax.jit(step_fn, donate_argnums=(0, 1))
    return init_fn, step, data_sharding


# ---------------------------------------------------------------------------
# Serving path (KV cache). One step function serves prefill (chunk C>1),
# incremental decode (C=1), and SpecInfer tree-verify (explicit mask) —
# the TPU-native counterpart of the reference's three attention operators
# (inc/spec/tree_inc_multihead_self_attention, SURVEY.md §2.1): instead of
# three CUDA kernels there is one compiled XLA program per static
# (C, all_logits, mask-mode) signature, all sharing the same KV buffers.


def init_kv_cache(
    cfg: LLaMAConfig, num_slots: int, max_len: int, dtype=None
) -> Dict[str, jnp.ndarray]:
    """KV cache pytree: (L, slots, max_len+1, KV, dk). The last position is
    a scratch row — padding tokens scatter there so real cache lines are
    never corrupted (replaces the reference's per-request contiguous cache
    with request-slot paging, inc_multihead_self_attention.cu:1338)."""
    L, KV, dk = cfg.num_hidden_layers, cfg.num_key_value_heads, cfg.head_dim
    dt = dtype or cfg.dtype
    shape = (L, num_slots, max_len + 1, KV, dk)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def kv_cache_pspecs(
    cfg: Optional[LLaMAConfig] = None, *, pipeline: bool = False
) -> Dict[str, P]:
    """Cache shards over TP on the KV-head dim (same axis the attention
    heads shard on) and over DP on the slot dim; with ``pipeline`` the
    layer-major leading dim shards over ``pipe`` so each stage holds the
    cache for its own layers."""
    pp = PIPE_AXIS if pipeline else None
    return {
        "k": P(pp, DATA_AXIS, None, MODEL_AXIS, None),
        "v": P(pp, DATA_AXIS, None, MODEL_AXIS, None),
    }


def serve_attention(cfg: LLaMAConfig, q, k_cache, v_cache, mask):
    """Grouped-query attention of q (R, C, H, dk) against the full cache
    (R, S, KV, dk) without materialising the GQA head repeat: q is viewed
    as (R, C, KV, G, dk) and contracted per KV group."""
    R, C, H, dk = q.shape
    KV = cfg.num_key_value_heads
    G = H // KV
    qg = q.reshape(R, C, KV, G, dk)
    scores = jnp.einsum(
        "rckgd,rskd->rkgcs", qg, k_cache, preferred_element_type=jnp.float32
    ) / math.sqrt(cfg.head_dim)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("rkgcs,rskd->rckgd", probs, v_cache)
    return out.reshape(R, C, H * dk)


def serve_block(cfg: LLaMAConfig, p, x, cos, sin, mask, k_cache, v_cache,
                positions, kernels: str = "xla"):
    """One transformer block on a serving step: project, RoPE, scatter new
    K/V into the cache at ``positions`` (cache line indices — for tree
    tokens these differ from the RoPE positions baked into cos/sin),
    attend over the whole cache. ``kernels="pallas"`` routes attention
    through the fused flash-style TPU kernels (serve/kernels.py: decode
    for C==1, tree-verify otherwise — the reference's
    inc/tree_inc_multihead_self_attention CUDA kernels)."""
    R, C, D = x.shape
    H, KV, dk = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    h = _rms(x, p["attn_norm"], cfg.rms_norm_eps)
    q = _mm(h, p["wq"]).reshape(R, C, H, dk)
    k = _mm(h, p["wk"]).reshape(R, C, KV, dk)
    v = _mm(h, p["wv"]).reshape(R, C, KV, dk)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    bidx = jnp.arange(R)[:, None]
    k_cache = k_cache.at[bidx, positions].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, positions].set(v.astype(v_cache.dtype))
    if kernels == "pallas":
        from ..serve import kernels as _pk

        if C == 1:
            seq_lens = mask[:, 0, :].sum(axis=-1).astype(jnp.int32)
            attn = _pk.decode_attention(q[:, 0], k_cache, v_cache, seq_lens)
            attn = attn.reshape(R, 1, H * dk)
        else:
            attn = _pk.verify_attention(q, k_cache, v_cache, mask)
            attn = attn.reshape(R, C, H * dk)
    else:
        attn = serve_attention(cfg, q, k_cache, v_cache, mask)
    x = x + _mm(attn, p["wo"])
    h2 = _rms(x, p["ffn_norm"], cfg.rms_norm_eps)
    ffn = _mm(jax.nn.silu(_mm(h2, p["w1"])) * _mm(h2, p["w3"]), p["w2"])
    return x + ffn, k_cache, v_cache


def serve_step(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,     # (R, C) int32; padding points at scratch pos
    positions: jnp.ndarray,  # (R, C) int32 RoPE/sequence positions
    logits_idx: jnp.ndarray, # (R,) int32 chunk index whose logits to return
    mask: Optional[jnp.ndarray],  # (R, C, S+1) bool, or None => causal
    cache_positions: Optional[jnp.ndarray] = None,  # (R, C) cache line idx
    *,
    cfg: LLaMAConfig,
    all_logits: bool = False,
    kernels: str = "xla",
    num_layers: Optional[int] = None,
    mesh=None,
):
    """One serving step over R request slots × C tokens each.

    ``cache_positions`` defaults to ``positions``; SpecInfer passes them
    separately because sibling tree tokens share a sequence position
    (prefix + depth) but need distinct cache lines (prefix + node index).

    With a ``mesh`` whose pipe axis is >1, the layer stack (and the
    layer-major KV cache) is stage-sharded and activations flow through
    the pipeline (reference inference_manager.cc:91-133 stage mapping).

    ``num_layers`` runs a LAYER-SLICED step: only the first
    ``num_layers`` blocks execute (their K/V commit into the cache; the
    deeper layers' cache buffers pass through untouched) before the
    full model's final norm + head read the truncated hidden state —
    the self-speculation "early-exit" draft (LayerSkip-style,
    SpecConfig.draft="early_exit"): the target's own shallow prefix
    drafts tokens the full-depth verify pass then re-checks. None
    (default) = the full stack.

    Returns (logits, new_cache): logits (R, V) at ``logits_idx`` or
    (R, C, V) when ``all_logits`` (tree verification needs every token's
    logits, reference tree_inc_multihead_self_attention.cu).
    """
    R, C = tokens.shape
    S1 = cache["k"].shape[2]  # max_len + 1 (scratch row)
    if cache_positions is None:
        cache_positions = positions
    x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
    cos, sin = rope_freqs(cfg, positions)
    if mask is None:
        # Causal-by-position (serve/kernels.causal_serve_mask): a token
        # attends every cache line at position <= its own. Only
        # positions already written satisfy this, so stale lines from an
        # evicted request are never read.
        from ..serve.kernels import causal_serve_mask

        mask = causal_serve_mask(positions, S1)

    def scan_body(h, xs):
        p_l, kc, vc = xs
        h, kc, vc = serve_block(
            cfg, p_l, h, cos, sin, mask, kc, vc, cache_positions, kernels
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

        def stage_fn(stage_layers, caches, h, row):
            kc, vc = caches

            def body(hh, xs):
                p_l, kcl, vcl = xs
                hh, kcl, vcl = serve_block(
                    cfg, p_l, hh, row["cos"], row["sin"], row["mask"],
                    kcl, vcl, row["cpos"], kernels,
                )
                return hh, (kcl, vcl)

            h, (kc, vc) = lax.scan(body, h, (stage_layers, kc, vc))
            return h, (kc, vc)

        row = {"cos": cos, "sin": sin, "mask": mask, "cpos": cache_positions}
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
        # deeper layers never run: their cache rows pass through intact
        # (the verify pass owns them)
        k_new = jnp.concatenate([k_upd, cache["k"][n:]], axis=0)
        v_new = jnp.concatenate([v_upd, cache["v"][n:]], axis=0)
    else:
        x, (k_new, v_new) = lax.scan(
            scan_body, x, (params["layers"], cache["k"], cache["v"])
        )
    x = _rms(x, params["final_norm"], cfg.rms_norm_eps)
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    if not all_logits:
        x = jnp.take_along_axis(x, logits_idx[:, None, None], axis=1)  # (R,1,D)
        logits = jnp.matmul(x, head, preferred_element_type=jnp.float32)[:, 0]
    else:
        logits = jnp.matmul(x, head, preferred_element_type=jnp.float32)
    return logits, {"k": k_new, "v": v_new}


def serve_debug_activations(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    cache_positions: Optional[jnp.ndarray] = None,
    *,
    cfg: LLaMAConfig,
    kernels: str = "xla",
    page_table: Optional[jnp.ndarray] = None,
    cache_len: Optional[int] = None,
    kv_quant: Optional[str] = None,
):
    """Per-layer hidden-state capture for ``inference_debugging``
    (reference's per-op tensor dump mode, serve/__init__.py:48 —
    saving all inputs/outputs to file for serving triage). Runs the
    layer stack as an eager Python loop instead of ``lax.scan`` so every
    layer's output survives as its own array; cache writes are computed
    and DISCARDED (the caller's donating step does the real commit).
    Deliberately slow — a triage tool, not a serving path. With
    ``page_table`` the paged layout is read/written through the table
    (``kv_quant``: the quantized pool, dequantized per layer)."""
    if cache_positions is None:
        cache_positions = positions
    x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
    cos, sin = rope_freqs(cfg, positions)
    acts = []
    if page_table is not None:  # paged layout
        ps = cache["k"].shape[2]
        mask = _paged_mask(mask, positions, page_table, ps, cache_len)
        phys, off = _page_lookup(page_table, cache_positions, ps)
        qmax = None
        if kv_quant is not None:
            from ..serve.kv_quant import resolve_spec

            qmax = resolve_spec(kv_quant).qmax
        for l in range(cfg.num_hidden_layers):
            p_l = jax.tree.map(lambda a: a[l], params["layers"])
            x, *_ = serve_block_paged(
                cfg, p_l, x, cos, sin, mask,
                cache["k"][l], cache["v"][l], phys, off, page_table,
                kernels,
                cache["k_scale"][l] if qmax is not None else None,
                cache["v_scale"][l] if qmax is not None else None,
                qmax,
            )
            acts.append(x)
        return acts
    S1 = cache["k"].shape[2]
    if mask is None:
        from ..serve.kernels import causal_serve_mask

        mask = causal_serve_mask(positions, S1)
    for l in range(cfg.num_hidden_layers):
        p_l = jax.tree.map(lambda a: a[l], params["layers"])
        x, _, _ = serve_block(
            cfg, p_l, x, cos, sin, mask,
            cache["k"][l], cache["v"][l], cache_positions, kernels,
        )
        acts.append(x)
    return acts


# ---------------------------------------------------------------------------
# Paged serving path (Ragged Paged Attention layout, PAPERS.md arxiv
# 2604.15464): K/V live in a pool of fixed-size token pages shared by all
# request slots; each slot's page table maps logical cache lines
# (line // page_size) to physical pages. HBM is proportional to pages
# allocated — live tokens — instead of slots × max_len, which is what
# lets one chip serve the reference's 64 request slots. The XLA path
# gathers the virtual cache through the table with ``jnp.take`` and runs
# the exact dense serve_attention math (bit-for-bit parity with the
# dense layout); ``kernels="pallas"`` routes through the fused ragged
# paged kernel (serve/kernels.py) which DMAs pages directly.

#: decode-step fusions this family's serving step supports
#: (ServingConfig.fused_decode; the engine validates requests against
#: this). "rope_kv_write": serve_step_paged folds RoPE + the KV page
#: write into the ragged paged Pallas kernel. (The sampling head is no
#: fusion: the engine's step program holds the one its batch needs.)
FUSED_DECODE = ("rope_kv_write",)


def init_paged_kv_cache(
    cfg: LLaMAConfig, num_pages: int, page_size: int, dtype=None,
    kv_quant: Optional[str] = None, extra_rows: int = 0,
) -> Dict[str, jnp.ndarray]:
    """Paged pool: (L, num_pages+1, page_size, KV, dk). Pool row
    ``num_pages`` is the shared scratch page — unallocated page-table
    entries point there, so padding writes and gathers through
    unallocated entries never touch live pages (the paged analog of the
    dense layout's per-slot scratch row).

    With ``kv_quant`` (serve/kv_quant.py) the pools store quantized
    codes — int8, or packed int4 nibbles (two codes per byte along dk,
    so the trailing dim is ``head_dim // 2``) — and the cache gains
    ``k_scale``/``v_scale``: (L, num_pages+1, KV) f32
    per-page-per-KV-head amax scales, zero-initialised (a zero scale
    marks a page with no committed lines).

    ``extra_rows`` appends never-referenced pad rows AFTER the scratch
    row — context-parallel serving (ServingConfig.kv_shard="context")
    shards pool rows over the mesh ``seq`` axis and pads the row count
    to a multiple of the shard degree; no table entry ever points past
    the scratch row, so the pads are pure alignment."""
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
    return cache


def paged_kv_cache_pspecs(
    cfg: Optional[LLaMAConfig] = None, *, pipeline: bool = False,
    kv_quant: Optional[str] = None, kv_shard: Optional[str] = None,
) -> Dict[str, P]:
    """Pages shard over DP on the pool dim, KV heads over TP on the
    model axis (same head axis the attention shards on) — tensor-
    parallel serving keeps working; MQA (KV=1) replicates as in the
    dense layout. Quantized pools shard their per-page scale rows the
    same way (pages on data, KV heads on model). With
    ``kv_shard="context"`` pool rows shard over the SEQ axis instead —
    each sequence shard holds its own slice of one request's pages
    (ring ragged paged attention reads them locally;
    serve/kernels.ring_ragged_paged_attention)."""
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
    return specs


def _page_lookup(page_table: jnp.ndarray, cache_positions: jnp.ndarray,
                 page_size: int):
    """(R, NP) table × (R, C) cache lines → physical page + in-page
    offset, each (R, C)."""
    logical = cache_positions // page_size
    phys = jnp.take_along_axis(page_table, logical, axis=1)
    return phys, cache_positions % page_size


def _block_paged_xla(cfg: LLaMAConfig, p, x, cos, sin, mask,
                     k_pool, v_pool, phys, off, page_table,
                     k_scale=None, v_scale=None, qmax=None):
    """One block of the UNFUSED XLA paged step, on values: project,
    RoPE, commit K/V at the table-resolved (page, offset) — quantizing
    at the page scales when ``qmax`` is set — gather the virtual cache
    through the table, attend, out-project, FFN: the body of
    :func:`serve_block_paged`'s ``kernels="xla"`` path."""
    dk = cfg.head_dim
    R, C, D = x.shape
    h = _rms(x, p["attn_norm"], cfg.rms_norm_eps)
    q = _mm(h, p["wq"]).reshape(R, C, -1, dk)
    k = _mm(h, p["wk"]).reshape(R, C, -1, dk)
    v = _mm(h, p["wv"]).reshape(R, C, -1, dk)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if qmax is not None:
        from ..serve.kv_quant import quant_line_write

        k_pool, k_scale = quant_line_write(k_pool, k_scale, phys, off, k,
                                           qmax)
        v_pool, v_scale = quant_line_write(v_pool, v_scale, phys, off, v,
                                           qmax)
    else:
        k_pool = k_pool.at[phys, off].set(k.astype(k_pool.dtype))
        v_pool = v_pool.at[phys, off].set(v.astype(v_pool.dtype))
    from ..serve import kernels as _pk

    if qmax is not None:
        k_virt = _pk.dequant_pages(k_pool, k_scale, page_table, q.dtype)
        v_virt = _pk.dequant_pages(v_pool, v_scale, page_table, q.dtype)
    else:
        k_virt = _pk.gather_pages(k_pool, page_table)
        v_virt = _pk.gather_pages(v_pool, page_table)
    attn = serve_attention(cfg, q, k_virt, v_virt, mask)
    x = x + _mm(attn, p["wo"])
    h2 = _rms(x, p["ffn_norm"], cfg.rms_norm_eps)
    ffn = _mm(jax.nn.silu(_mm(h2, p["w1"])) * _mm(h2, p["w3"]), p["w2"])
    return x + ffn, k_pool, v_pool, k_scale, v_scale


def serve_block_paged(cfg: LLaMAConfig, p, x, cos, sin, mask,
                      k_pool, v_pool, phys, off, page_table,
                      kernels: str = "xla",
                      k_scale=None, v_scale=None, qmax=None,
                      *, fused_rope: bool = False, logical=None,
                      cp_mesh=None):
    """One block on a paged serving step: scatter new K/V at the
    table-resolved (physical page, offset), attend over the virtual
    cache read through the page table. With ``qmax`` (quantized pool,
    serve/kv_quant.py) the KV commit quantizes in the step itself —
    per-page amax scales, rescale-on-growth — and attention dequantizes
    at read time (in-kernel on the Pallas path), so full-precision K/V
    never round-trip HBM. Returns
    ``(x, k_pool, v_pool, k_scale, v_scale)`` (scales None when the
    pool is full-precision).

    ``fused_rope`` (the megakernel decode step,
    ``ServingConfig.fused_decode``): on the Pallas path the RoPE on
    Q/K and the (optionally quantizing) KV page write move INSIDE the
    ragged paged kernel (serve/kernels.fused_rope_paged_attention) —
    the fresh K/V lines never round-trip HBM between this block's
    projection and its attention read. Bitwise-identical to the
    unfused composition below; on kernels="xla" the flag is a no-op
    because the unfused XLA step IS the CPU-parity fallback. On a
    sequence-sharded mesh (``cp_mesh``) the fused prologue joins the
    RING body instead (PR-11's exclusion, lifted): each shard rotates
    Q/K and commits its resident lines inside the shard_map program —
    serve/kernels.ring_ragged_paged_attention's ``fused`` mode."""
    R, C, D = x.shape
    H, KV, dk = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    from ..serve import kernels as _pk

    if cp_mesh is None and kernels != "pallas":
        # the unfused XLA path — the CPU-parity reference every fusion
        # anchors on
        return _block_paged_xla(
            cfg, p, x, cos, sin, mask, k_pool, v_pool, phys, off,
            page_table, k_scale, v_scale, qmax,
        )
    h = _rms(x, p["attn_norm"], cfg.rms_norm_eps)
    q = _mm(h, p["wq"]).reshape(R, C, H, dk)
    k = _mm(h, p["wk"]).reshape(R, C, KV, dk)
    v = _mm(h, p["wv"]).reshape(R, C, KV, dk)

    if fused_rope and kernels == "pallas" and cp_mesh is None:
        attn, k_pool, v_pool, k_scale, v_scale = (
            _pk.fused_rope_paged_attention(
                q, k, v, cos, sin, k_pool, v_pool, page_table,
                logical, off, mask,
                k_scale=k_scale, v_scale=v_scale, qmax=qmax,
            )
        )
        attn = attn.reshape(R, C, H * dk)
        x = x + _mm(attn, p["wo"])
        h2 = _rms(x, p["ffn_norm"], cfg.rms_norm_eps)
        ffn = _mm(jax.nn.silu(_mm(h2, p["w1"])) * _mm(h2, p["w3"]), p["w2"])
        return x + ffn, k_pool, v_pool, k_scale, v_scale
    if fused_rope and kernels == "pallas" and cp_mesh is not None:
        # ring fused prologue: RoPE + the resident-line commit move
        # inside the per-shard shard_map body (full-precision pools;
        # the quantized combination raises loudly in the kernel and is
        # excluded at ServingConfig validation)
        attn, k_pool, v_pool = _pk.ring_ragged_paged_attention(
            q, k_pool, v_pool, page_table, mask, cp_mesh,
            fused=dict(k_new=k, v_new=v, cos=cos, sin=sin,
                       phys=phys, off=off),
        )
        attn = attn.reshape(R, C, H * dk)
        x = x + _mm(attn, p["wo"])
        h2 = _rms(x, p["ffn_norm"], cfg.rms_norm_eps)
        ffn = _mm(jax.nn.silu(_mm(h2, p["w1"])) * _mm(h2, p["w3"]), p["w2"])
        return x + ffn, k_pool, v_pool, k_scale, v_scale
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if qmax is not None:
        from ..serve.kv_quant import quant_line_write

        k_pool, k_scale = quant_line_write(k_pool, k_scale, phys, off, k, qmax)
        v_pool, v_scale = quant_line_write(v_pool, v_scale, phys, off, v, qmax)
    else:
        k_pool = k_pool.at[phys, off].set(k.astype(k_pool.dtype))
        v_pool = v_pool.at[phys, off].set(v.astype(v_pool.dtype))
    if cp_mesh is not None:
        # context-parallel attention over the sequence-sharded pool:
        # each seq shard attends its resident pages, partial softmax
        # stats rotate via ppermute (the chunked-prefill KV write above
        # already landed on the owning shard — GSPMD routes the
        # replicated-index scatter to the sharded rows)
        attn = _pk.ring_ragged_paged_attention(
            q, k_pool, v_pool, page_table, mask, cp_mesh,
            k_scale=k_scale, v_scale=v_scale,
        )
        attn = attn.reshape(R, C, H * dk)
    else:  # kernels == "pallas" (the xla path returned above)
        attn = _pk.ragged_paged_attention(
            q, k_pool, v_pool, page_table, mask,
            k_scale=k_scale, v_scale=v_scale,
        )
        attn = attn.reshape(R, C, H * dk)
    x = x + _mm(attn, p["wo"])
    h2 = _rms(x, p["ffn_norm"], cfg.rms_norm_eps)
    ffn = _mm(jax.nn.silu(_mm(h2, p["w1"])) * _mm(h2, p["w3"]), p["w2"])
    return x + ffn, k_pool, v_pool, k_scale, v_scale


def _paged_mask(mask, positions, page_table, page_size, cache_len):
    """Default causal-by-position mask over the virtual cache, or an
    explicit (R, C, cache_len+1) mask padded out to the page-aligned
    virtual length (serve/kernels.paged_serve_mask — shared with the
    generic decoder)."""
    from ..serve.kernels import paged_serve_mask

    return paged_serve_mask(
        mask, positions, page_table.shape[1], page_size, cache_len
    )


def serve_step_paged(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,      # (R, C)
    positions: jnp.ndarray,   # (R, C) RoPE/sequence positions
    logits_idx: jnp.ndarray,  # (R,)
    mask: Optional[jnp.ndarray],  # (R, C, cache_len+1) bool or None
    cache_positions: Optional[jnp.ndarray],  # (R, C) cache line idx
    page_table: jnp.ndarray,  # (R, NP) int32
    *,
    cfg: LLaMAConfig,
    cache_len: int,
    all_logits: bool = False,
    kernels: str = "xla",
    kv_quant: Optional[str] = None,
    fused_rope: bool = False,
    num_layers: Optional[int] = None,
    mesh=None,
    cp_mesh=None,
):
    """Paged twin of :func:`serve_step` — same contract plus the
    per-slot page table; prefill chunks, single-token decode and
    tree-verify all read/write K/V through the table. ``kv_quant``
    selects the quantized pool layout (serve/kv_quant.py): the KV
    commit quantizes in-step and attention dequantizes at read time.
    ``fused_rope`` (megakernel decode step) folds RoPE and the KV page
    write into the Pallas kernel per block — a no-op on the XLA path,
    which already is the fused variants' CPU-parity reference.
    ``num_layers`` is the layer-sliced early-exit draft step (see
    :func:`serve_step`): only the first ``num_layers`` blocks run and
    commit K/V; deeper pool rows (and their quant scale rows) pass
    through untouched for the verify pass to own. ``cp_mesh`` (context
    parallelism, ServingConfig.kv_shard="context" on a sequence-
    sharded mesh) routes every block's attention through ring ragged
    paged attention over the seq-sharded pool
    (serve/kernels.ring_ragged_paged_attention)."""
    if mesh is not None and mesh.shape.get(PIPE_AXIS, 1) > 1:
        raise NotImplementedError(
            "paged KV serving is not composed with pipeline parallelism "
            "yet — use kv_layout='dense' with pipe>1"
        )
    if cache_positions is None:
        cache_positions = positions
    ps = cache["k"].shape[2]
    x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
    cos, sin = rope_freqs(cfg, positions)
    mask = _paged_mask(mask, positions, page_table, ps, cache_len)
    phys, off = _page_lookup(page_table, cache_positions, ps)
    logical = cache_positions // ps

    n = cfg.num_hidden_layers
    if num_layers is not None:
        n = min(num_layers, n)
    sliced = n < cfg.num_hidden_layers
    layers = (
        jax.tree.map(lambda a: a[:n], params["layers"])
        if sliced else params["layers"]
    )

    if kv_quant is not None:
        from ..serve.kv_quant import resolve_spec

        qmax = resolve_spec(kv_quant).qmax

        def scan_body_q(h, xs):
            p_l, kc, vc, ks, vs = xs
            h, kc, vc, ks, vs = serve_block_paged(
                cfg, p_l, h, cos, sin, mask, kc, vc, phys, off,
                page_table, kernels, ks, vs, qmax,
                fused_rope=fused_rope, logical=logical, cp_mesh=cp_mesh,
            )
            return h, (kc, vc, ks, vs)

        x, (k_new, v_new, ks_new, vs_new) = lax.scan(
            scan_body_q, x,
            (layers, cache["k"][:n], cache["v"][:n],
             cache["k_scale"][:n], cache["v_scale"][:n]),
        )
        if sliced:
            k_new = jnp.concatenate([k_new, cache["k"][n:]], axis=0)
            v_new = jnp.concatenate([v_new, cache["v"][n:]], axis=0)
            ks_new = jnp.concatenate([ks_new, cache["k_scale"][n:]], axis=0)
            vs_new = jnp.concatenate([vs_new, cache["v_scale"][n:]], axis=0)
        new_cache = {"k": k_new, "v": v_new,
                     "k_scale": ks_new, "v_scale": vs_new}
    else:
        def scan_body(h, xs):
            p_l, kc, vc = xs
            h, kc, vc, _, _ = serve_block_paged(
                cfg, p_l, h, cos, sin, mask, kc, vc, phys, off,
                page_table, kernels,
                fused_rope=fused_rope, logical=logical, cp_mesh=cp_mesh,
            )
            return h, (kc, vc)

        x, (k_new, v_new) = lax.scan(
            scan_body, x, (layers, cache["k"][:n], cache["v"][:n])
        )
        if sliced:
            k_new = jnp.concatenate([k_new, cache["k"][n:]], axis=0)
            v_new = jnp.concatenate([v_new, cache["v"][n:]], axis=0)
        new_cache = {"k": k_new, "v": v_new}
    x = _rms(x, params["final_norm"], cfg.rms_norm_eps)
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    if not all_logits:
        x = jnp.take_along_axis(x, logits_idx[:, None, None], axis=1)
        logits = jnp.matmul(x, head, preferred_element_type=jnp.float32)[:, 0]
    else:
        logits = jnp.matmul(x, head, preferred_element_type=jnp.float32)
    return logits, new_cache


def copy_page_kv(
    cache: Dict[str, jnp.ndarray],
    src: jnp.ndarray,  # () int32 physical page
    dst: jnp.ndarray,  # () int32 physical page
) -> Dict[str, jnp.ndarray]:
    """Copy one physical page's K/V lines (all layers) to another page —
    the device half of prefix-cache copy-on-write (serve/
    prefix_cache.py): a request appending into a shared cached tail page
    writes into a private copy, never the cached original. Dtype-
    agnostic by construction: every cache buffer — bf16 or int8 pools
    AND the quantized layout's (L, P+1, KV) scale rows — copies through
    the same pool-row gather/scatter, so COW moves codes and their
    scales together byte-for-byte."""
    return {
        name: buf.at[:, dst].set(buf[:, src])  # (L, P+1, ps|KV, ...)
        for name, buf in cache.items()
    }


def gather_page_kv(
    cache: Dict[str, jnp.ndarray],
    page: jnp.ndarray,  # () int32 physical page
) -> Dict[str, jnp.ndarray]:
    """Slice one physical page's content out of every cache buffer —
    the device half of a hierarchical-KV SPILL (serve/prefix_cache.py
    host tier): the engine starts an async device→host copy on the
    returned pytree and the page returns to the free list. Covers K/V
    pools AND the quantized layout's per-page scale rows, so a spilled
    page re-admits byte-for-byte."""
    return {name: buf[:, page] for name, buf in cache.items()}


def scatter_page_kv(
    cache: Dict[str, jnp.ndarray],
    page: jnp.ndarray,  # () int32 physical page
    values: Dict[str, jnp.ndarray],
) -> Dict[str, jnp.ndarray]:
    """Write a previously spilled page's content (the pytree
    :func:`gather_page_kv` produced) into pool row ``page`` — the
    device half of a host-tier RE-ADMIT. Exact inverse of the gather:
    codes and scales land byte-for-byte, which is what keeps
    spilled-then-readmitted generation bitwise identical to the
    never-evicted warm path."""
    return {
        name: buf.at[:, page].set(values[name])
        for name, buf in cache.items()
    }


def commit_kv_paged(
    cache: Dict[str, jnp.ndarray],
    page_table: jnp.ndarray,  # (R, NP) int32
    src: jnp.ndarray,         # (R, K) int32 cache lines (tree node lines)
    dst: jnp.ndarray,         # (R, K) int32 destination lines
    *,
    kv_quant: Optional[str] = None,
) -> Dict[str, jnp.ndarray]:
    """:func:`commit_kv` through the page table: accepted speculative
    lines move between table-resolved (page, offset) pairs. Functional
    gather-then-scatter, so overlapping ranges stay safe; scratch→
    scratch no-ops are harmless duplicates (identical values).

    On a quantized pool the codes cannot move verbatim (source and
    destination pages carry different scales): the lines dequantize at
    their source page's scale and re-commit through the standard
    quantized write (serve/kv_quant.quant_commit_lines), updating the
    destination pages' amax scales exactly as a fresh write would."""
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
        return out
    out = {}
    for name, buf in cache.items():  # (L, P+1, ps, KV, dk)
        rows = buf[:, s_phys, s_off]  # (L, R, K, KV, dk)
        out[name] = buf.at[:, d_phys, d_off].set(rows)
    return out


def reorder_slots_paged(
    cache: Dict[str, jnp.ndarray],
    page_table: jnp.ndarray,  # (R, NP) int32
    src: jnp.ndarray,         # (R,) int32
) -> Dict[str, jnp.ndarray]:
    """:func:`reorder_slots` for the paged layout: page OWNERSHIP stays
    with each slot (the host table is untouched) and page CONTENT is
    copied — new slot r's pages receive slot src[r]'s lines. Requires
    the destination slots to have (at least) the source slots' pages
    allocated, which beam search guarantees by construction (equal-
    length hypotheses)."""
    src_pages = page_table[src].reshape(-1)   # (R*NP,)
    dst_pages = page_table.reshape(-1)
    return {
        name: buf.at[:, dst_pages].set(buf[:, src_pages])
        for name, buf in cache.items()
    }


def commit_kv(
    cache: Dict[str, jnp.ndarray],
    src: jnp.ndarray,  # (R, K) int32 cache lines to keep (tree node lines)
    dst: jnp.ndarray,  # (R, K) int32 destination lines (contiguous suffix)
) -> Dict[str, jnp.ndarray]:
    """Move accepted speculative K/V lines into their committed positions
    — the TPU-native version of the reference's token-commit copy kernels
    (reference ``request_manager.cu`` commit_tokens + the KV-cache commit
    in ``tree_inc_multihead_self_attention.cu``). Unused slots should map
    scratch→scratch. Functional gather-then-scatter, so overlapping
    src/dst ranges are safe."""
    R = src.shape[0]
    bidx = jnp.arange(R)[:, None]
    out = {}
    for name, buf in cache.items():  # (L, R, S1, KV, dk)
        rows = buf[:, bidx, src]     # (L, R, K, KV, dk)
        out[name] = buf.at[:, bidx, dst].set(rows)
    return out


def reorder_slots(
    cache: Dict[str, jnp.ndarray], src: jnp.ndarray  # (R,) int32
) -> Dict[str, jnp.ndarray]:
    """Gather cache slots: new slot r takes slot src[r]'s lines — beam
    search reorders hypotheses across request slots this way (the
    reference's beam attention forks sub-request KV instead,
    spec_inc_multihead_self_attention.cu)."""
    return {name: buf[:, src] for name, buf in cache.items()}


def num_params(cfg: LLaMAConfig) -> int:
    L, D, F, V = (
        cfg.num_hidden_layers,
        cfg.hidden_size,
        cfg.intermediate_size,
        cfg.vocab_size,
    )
    H, KV, dk = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    per_layer = D * (H * dk) + 2 * D * (KV * dk) + (H * dk) * D + 3 * D * F + 2 * D
    head = 0 if cfg.tie_word_embeddings else D * V
    return V * D + L * per_layer + D + head


def flops_per_token(cfg: LLaMAConfig, seq_len: int) -> int:
    """Forward FLOPs/token ≈ 2*n_params + attention quadratic term."""
    return 2 * num_params(cfg) + 4 * cfg.num_hidden_layers * cfg.hidden_size * seq_len


def convert_hf_state_dict(sd: Dict[str, Any], cfg: LLaMAConfig) -> Dict[str, Any]:
    """HF ``LlamaForCausalLM`` state dict → framework pytree (stacked
    layer dim). The analog of the reference's per-layer weight-file
    conversion (reference ``python/flexflow/serve/serve.py:167-227``,
    ``inference/file_loader.cc:792``)."""
    from .hf_utils import linear_w, stack, to_np

    dt = cfg.dtype
    L = cfg.num_hidden_layers
    pre = "model."

    def mats(fmt):
        return stack([linear_w(sd, pre + fmt.format(i)) for i in range(L)], dt)

    def vecs(fmt):
        return stack([to_np(sd[pre + fmt.format(i)]) for i in range(L)], dt)

    layers = {
        "attn_norm": vecs("layers.{}.input_layernorm.weight"),
        "wq": mats("layers.{}.self_attn.q_proj.weight"),
        "wk": mats("layers.{}.self_attn.k_proj.weight"),
        "wv": mats("layers.{}.self_attn.v_proj.weight"),
        "wo": mats("layers.{}.self_attn.o_proj.weight"),
        "ffn_norm": vecs("layers.{}.post_attention_layernorm.weight"),
        "w1": mats("layers.{}.mlp.gate_proj.weight"),
        "w2": mats("layers.{}.mlp.down_proj.weight"),
        "w3": mats("layers.{}.mlp.up_proj.weight"),
    }
    params = {
        "embed": jnp.asarray(to_np(sd[pre + "embed_tokens.weight"]), dt),
        "layers": layers,
        "final_norm": jnp.asarray(to_np(sd[pre + "norm.weight"]), dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(linear_w(sd, "lm_head.weight"), dt)
    return params


def from_hf(hf: Dict[str, Any], **kw) -> LLaMAConfig:
    """Module-level alias so the family registry has a uniform
    ``from_hf`` entry point across model modules."""
    return LLaMAConfig.from_hf(hf, **kw)

"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has **no** sequence parallelism (SURVEY.md §2.2: closest
is variable-seq-len batch_matmul); long context is a required new
capability of the TPU framework (SURVEY.md §7 step 7). Two standard
schemes, both as `shard_map` primitives over the ``seq`` mesh axis:

  * :func:`ring_attention` — K/V blocks rotate around the ICI ring via
    ``ppermute`` while each device keeps its query block resident,
    accumulating softmax online (flash-attention style m/l/o carry).
    Memory per device stays O(S/n); comm overlaps with the next block's
    compute in XLA's scheduler. Causality is enforced from global block
    positions, so later K/V blocks are masked without materialising an
    S×S mask.
  * :func:`ulysses_attention` — all-to-all re-shards (B, S/n, H, d) →
    (B, S, H/n, d), runs plain attention on whole sequences for a head
    subset, and all-to-alls back. Cheaper comm volume for moderate S;
    requires heads % seq_degree == 0.

Both compute attention exactly (they are layout transforms + online
softmax), so tests assert bit-level-ish equality with the dense
reference implementation.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..core.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
    shard_map_unchecked as _shard_map_unchecked,
)

# core.mesh.shard_map_unchecked is the one shard_map entry for every
# collective primitive (see its docstring for why the static replication
# checker is off).


def _online_block(q, k, v, o, m, l, qpos, kpos, scale, causal, kv_len=None):
    """One K/V block of online-softmax attention.

    q (B,Sq,H,d) f.* ; k/v (B,Sk,H,d); o (B,Sq,H,d) f32 accumulator;
    m/l (B,H,Sq) running max / denominator (f32). ``kv_len`` masks
    padded K/V positions (global kpos >= kv_len) when the sequence was
    right-padded to a multiple of the seq-axis degree.
    """
    scores = jnp.einsum(
        "bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = None
    if causal:
        mask = kpos[None, :] <= qpos[:, None]  # (Sq, Sk)
    if kv_len is not None:
        kv_valid = jnp.broadcast_to(kpos[None, :] < kv_len, (qpos.shape[0], kpos.shape[0]))
        mask = kv_valid if mask is None else (mask & kv_valid)
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # fully-masked rows keep m=-inf; guard the exp against -inf - -inf
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(scores), scores - safe_m[..., None], -jnp.inf))
    p = jnp.where(jnp.isfinite(p), p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), jnp.where(m_new == m, 1.0, 0.0))
    corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhst,bthd->bshd", p, v.astype(jnp.float32)
    )
    return o_new, m_new, l_new


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool, scale: float,
                          kv_len: Optional[int] = None):
    """Per-shard body (inside shard_map): local q stays, k/v rotate.
    K/V may carry fewer (GQA/MQA) heads than q — they rotate compact
    (H/KV× less ppermute traffic) and expand only inside the block."""
    n = lax.psum(1, axis_name)
    i = lax.axis_index(axis_name)
    B, S, H, d = q.shape
    rep = H // k.shape[2]
    qf = q.astype(jnp.float32)
    q_pos = i * S + jnp.arange(S)

    def body(step, carry):
        o, m, l, kk, vv = carry
        j = (i - step) % n
        k_pos = j * S + jnp.arange(S)
        ke = jnp.repeat(kk, rep, axis=2) if rep > 1 else kk
        ve = jnp.repeat(vv, rep, axis=2) if rep > 1 else vv
        o, m, l = _online_block(qf, ke, ve, o, m, l, q_pos, k_pos, scale, causal, kv_len)
        perm = [(s, (s + 1) % n) for s in range(n)]
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return o, m, l, kk, vv

    # derive accumulators from q so they carry the same varying-manual-axes
    # type as loop-computed values (shard_map tracks axis provenance)
    o0 = jnp.zeros_like(qf)
    m0 = jnp.full_like(qf[..., 0].transpose(0, 2, 1), -jnp.inf)  # (B, H, S)
    l0 = jnp.zeros_like(m0)
    o, m, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    l = jnp.maximum(l, 1e-20)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # (B, S, H, d) — S sharded on the seq axis
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    shard_heads: bool = True,
) -> jnp.ndarray:
    """Exact attention with the sequence dim sharded over ``seq`` and
    (optionally) heads over ``model``. K/V may carry fewer heads
    (GQA/MQA) — they rotate compact and expand per block."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    h_axis = MODEL_AXIS if shard_heads else None
    if shard_heads and mesh.shape[MODEL_AXIS] > 1:
        if k.shape[2] % mesh.shape[MODEL_AXIS]:
            # K/V rotate COMPACT around the ring (GQA heads expand only
            # inside each block), so the KV-head dim itself must split
            # over the model axis. Name the fixes that actually resolve
            # it: expand K/V to the full head count BEFORE calling
            # (jnp.repeat — trades the compact-rotation bandwidth win
            # for shardability), lower the tensor-parallel (model)
            # degree to a divisor of the KV head count, or pass
            # shard_heads=False and take the seq-only sharding.
            raise ValueError(
                f"GQA ring attention shards KV heads over the model "
                f"axis, but {k.shape[2]} KV heads do not divide by the "
                f"model degree ({mesh.shape[MODEL_AXIS]}). Fix: repeat "
                f"K/V to the full {q.shape[2]} heads before the call, "
                f"lower the tensor-parallel degree to a divisor of "
                f"{k.shape[2]}, or pass shard_heads=False"
            )
    n_seq = mesh.shape[SEQ_AXIS]
    S = q.shape[1]
    pad = (-S) % n_seq  # shard_map needs S % n_seq == 0: right-pad + mask
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v))
    qspec = P(DATA_AXIS, SEQ_AXIS, h_axis, None)
    fn = _shard_map_unchecked(
        functools.partial(
            _ring_attention_local, axis_name=SEQ_AXIS, causal=causal, scale=scale,
            kv_len=S if pad else None,
        ),
        mesh=mesh,
        in_specs=(qspec, qspec, qspec),
        out_specs=qspec,
    )
    out = fn(q, k, v)
    return out[:, :S] if pad else out


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, scale: float,
                   kv_len: Optional[int] = None):
    """Per-shard body: all-to-all seq→heads, dense attention, back."""
    n = lax.psum(1, axis_name)

    def to_heads(x):  # (B, S/n, H, d) -> (B, S, H/n, d)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):  # inverse
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    rep = q.shape[2] // k.shape[2]
    if rep > 1 and k.shape[2] % n == 0:
        # keep K/V compact through the all-to-all, expand after
        kh = jnp.repeat(to_heads(k), rep, axis=2)
        vh = jnp.repeat(to_heads(v), rep, axis=2)
    elif rep > 1:
        kh = to_heads(jnp.repeat(k, rep, axis=2))
        vh = to_heads(jnp.repeat(v, rep, axis=2))
    else:
        kh, vh = to_heads(k), to_heads(v)
    qh = to_heads(q)
    B, S, Hn, d = qh.shape
    scores = jnp.einsum(
        "bshd,bthd->bhst", qh.astype(jnp.float32), kh, preferred_element_type=jnp.float32
    ) * scale
    mask = jnp.tril(jnp.ones((S, S), bool)) if causal else None
    if kv_len is not None:
        kv_valid = jnp.broadcast_to(jnp.arange(S)[None, :] < kv_len, (S, S))
        mask = kv_valid if mask is None else (mask & kv_valid)
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, vh.astype(jnp.float32))
    return to_seq(out.astype(q.dtype))


def ulysses_attention(
    q: jnp.ndarray,  # (B, S, H, d) — S sharded on the seq axis
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    shard_heads: bool = True,
) -> jnp.ndarray:
    """DeepSpeed-Ulysses-style SP: all-to-all head redistribution, then
    whole-sequence attention per head subset. Heads must divide by the
    seq degree (after any ``model``-axis head sharding)."""
    n_seq = mesh.shape[SEQ_AXIS]
    H = q.shape[2]
    if shard_heads:
        H = H // mesh.shape[MODEL_AXIS] if mesh.shape[MODEL_AXIS] > 1 else H
    assert H % n_seq == 0, (
        f"ulysses needs heads-per-TP-shard ({H}) divisible by seq degree ({n_seq})"
    )
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    h_axis = MODEL_AXIS if shard_heads else None
    S = q.shape[1]
    pad = (-S) % n_seq  # all_to_all needs S % n_seq == 0: right-pad + mask
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v))
    spec = P(DATA_AXIS, SEQ_AXIS, h_axis, None)
    fn = _shard_map_unchecked(
        functools.partial(
            _ulysses_local, axis_name=SEQ_AXIS, causal=causal, scale=scale,
            kv_len=S if pad else None,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    out = fn(q, k, v)
    return out[:, :S] if pad else out

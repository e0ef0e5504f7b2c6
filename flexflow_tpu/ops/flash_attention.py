"""Flash attention (fwd + custom-VJP bwd) as Pallas TPU kernels — the
training-path counterpart of the serving kernels in serve/kernels.py.

The reference's training attention is cuDNN MHA (reference
``src/ops/attention.cc``); its serving attentions are hand-written CUDA.
On TPU the XLA path materialises the (B, H, S, T) score tensor in HBM,
which caps MFU and sequence length; this kernel streams K/V blocks
through VMEM with an online softmax so scores never leave the chip, and
the backward pass recomputes them blockwise from the saved LSE — the
FlashAttention-2 schedule laid out for the MXU (128-aligned blocks,
f32 accumulators).

Layout: ``(B, S, H, dk)`` queries / ``(B, T, H, dk)`` keys+values (GQA
heads repeated by the caller, models/transformer.make_flash_attention). Non-TPU backends run ``interpret=True`` so the CPU-mesh
tests exercise the same code path numerically.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# m/l accumulators are stored lane-replicated at this width: TPU vector
# memory tiles are (sublane, 128); a (bq,) scalar column would occupy a
# full tile anyway, and replicated storage keeps every op elementwise
LANES = 128


def _interpret() -> bool:
    """Interpret mode is what the CPU backend gets (tests on the virtual
    mesh); on a TPU Mosaic compiles the kernel. Any other backend has
    no Pallas path here — an error, not a silent interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default JAX backend is {backend!r}"
    )


def _block_positions(block, block_len, shape, axis):
    """Absolute positions of block ``block``'s lines along ``axis``, as an
    int32 iota already in ``shape``. In-bounds masks are compared in the
    shape they are used in: Mosaic cannot reshape an i1 vector
    ("infer-vector-layout: unsupported shape cast")."""
    return block * block_len + jax.lax.broadcasted_iota(
        jnp.int32, shape, axis
    )


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                o_scr, m_scr, l_scr, *,
                block_q, block_k, total_q, total_k, causal, scale):
    i = pl.program_id(1)  # query block
    j = pl.program_id(2)  # kv block (innermost: accumulators carry over)

    @pl.when(j == 0)
    def _():
        o_scr[:] = jnp.zeros_like(o_scr)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    qpos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0
    )
    kpos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1
    )
    mask = (kpos < total_k) & (qpos < total_q)
    if causal:
        mask = mask & (qpos >= kpos)

    @pl.when(jnp.any(mask))
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        # zero padded K/V rows — 0·exp(NEG_INF)=0 still, but NaN padding
        # from out-of-bounds block reads would poison the products
        kvalid = _block_positions(j, block_k, (block_k, 1), 0) < total_k
        k = jnp.where(kvalid, k, 0.0)
        v = jnp.where(kvalid, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                   # (bq, bk)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]                           # (bq, LANES)
        m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_next)             # (bq, LANES)
        p = jnp.exp(s - m_next[:, :1])
        p = jnp.where(mask, p, 0.0)
        l_scr[:] = l_scr[:] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # (bq, dk)
        o_scr[:] = o_scr[:] * corr[:, :1] + pv
        m_scr[:] = m_next

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (o_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l)


def _variant(causal: bool) -> str:
    """Kernel-name suffix: what a profile or the HLO shows of a
    ``pallas_call`` is its ``name`` (``ff_flash_fwd_causal`` …)."""
    return "_causal" if causal else ""


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    """q (N, S, dk), k/v (N, T, dk) → (out (N, S, dk), lse (N, S, 1)).
    The per-row statistics (lse here, delta in the backward) travel as
    COLUMNS: a (1, bq) block of an (N, S) array is refused by the TPU
    lowering (second-to-last block dim neither a multiple of 8 nor the
    array's), and a (bq,) row would need a lane↔sublane relayout against
    the (bq, bk) scores; a (1, bq, 1) block is what the body uses."""
    N, S, dk = q.shape
    T = k.shape[1]
    bq, bk = min(block_q, S), min(block_k, T)
    grid = (N, pl.cdiv(S, bq), pl.cdiv(T, bk))
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_q=bq, block_k=bk, total_q=S, total_k=T,
            causal=causal, scale=scale,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((N, S, dk), q.dtype),
            jax.ShapeDtypeStruct((N, S, 1), jnp.float32),
        ),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, dk), lambda n, i, j: (n, i, 0)),
                pl.BlockSpec((1, bk, dk), lambda n, i, j: (n, j, 0)),
                pl.BlockSpec((1, bk, dk), lambda n, i, j: (n, j, 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, bq, dk), lambda n, i, j: (n, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda n, i, j: (n, i, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((bq, dk), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
            ],
        ),
        name=f"ff_flash_fwd{_variant(causal)}",
        interpret=_interpret(),
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dK/dV accumulate over query blocks, dQ over kv blocks —
# scores recomputed blockwise from the saved LSE (FlashAttention-2)


def _bwd_kv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_scr, dv_scr, *,
                   block_q, block_k, total_q, total_k, causal, scale):
    j = pl.program_id(1)  # kv block
    i = pl.program_id(2)  # query block (innermost)

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    qpos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0
    )
    kpos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1
    )
    mask = (kpos < total_k) & (qpos < total_q)
    if causal:
        mask = mask & (qpos >= kpos)

    @pl.when(jnp.any(mask))
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        # out-of-bounds block rows read unspecified values: 0·NaN from a
        # padded lse/delta would poison ds even where p is masked to 0
        qvalid = qpos < total_q                     # (bq, 1)
        lse = jnp.where(qvalid, lse_ref[0], 0.0)
        delta = jnp.where(qvalid, delta_ref[0], 0.0)
        do = jnp.where(qvalid, do, 0.0)
        q = jnp.where(qvalid, q, 0.0)  # ds.T @ q contracts the q rows
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        p = jnp.where(mask, p, 0.0)                 # (bq, bk)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # (bk, dk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # (bq, bk)
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # (bk, dk)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_q_kernel(q_ref, k_ref, do_ref, lse_ref, delta_ref, v_ref,
                  dq_ref, dq_scr, *,
                  block_q, block_k, total_q, total_k, causal, scale):
    i = pl.program_id(1)  # query block
    j = pl.program_id(2)  # kv block (innermost)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    qpos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0
    )
    kpos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1
    )
    mask = (kpos < total_k) & (qpos < total_q)
    if causal:
        mask = mask & (qpos >= kpos)

    @pl.when(jnp.any(mask))
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        qvalid = qpos < total_q
        kvalid = _block_positions(j, block_k, (block_k, 1), 0) < total_k
        lse = jnp.where(qvalid, lse_ref[0], 0.0)
        delta = jnp.where(qvalid, delta_ref[0], 0.0)
        do = jnp.where(qvalid, do, 0.0)
        k = jnp.where(kvalid, k, 0.0)  # ds @ k contracts the kv rows
        v = jnp.where(kvalid, v, 0.0)  # do @ v.T feeds ds at padded cols
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale               # (bq, bk)
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # (bq, dk)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, causal, scale, block_q, block_k):
    N, S, dk = q.shape
    T = k.shape[1]
    bq, bk = min(block_q, S), min(block_k, T)
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # (N, S, 1)

    dkv = pl.pallas_call(
        functools.partial(
            _bwd_kv_kernel, block_q=bq, block_k=bk, total_q=S, total_k=T,
            causal=causal, scale=scale,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((N, T, dk), k.dtype),
            jax.ShapeDtypeStruct((N, T, dk), v.dtype),
        ),
        grid_spec=pl.GridSpec(
            grid=(N, pl.cdiv(T, bk), pl.cdiv(S, bq)),
            in_specs=[
                pl.BlockSpec((1, bq, dk), lambda n, j, i: (n, i, 0)),
                pl.BlockSpec((1, bk, dk), lambda n, j, i: (n, j, 0)),
                pl.BlockSpec((1, bk, dk), lambda n, j, i: (n, j, 0)),
                pl.BlockSpec((1, bq, dk), lambda n, j, i: (n, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda n, j, i: (n, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda n, j, i: (n, i, 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, bk, dk), lambda n, j, i: (n, j, 0)),
                pl.BlockSpec((1, bk, dk), lambda n, j, i: (n, j, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((bk, dk), jnp.float32),
                pltpu.VMEM((bk, dk), jnp.float32),
            ],
        ),
        name=f"ff_flash_bwd_dkv{_variant(causal)}",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_q_kernel, block_q=bq, block_k=bk, total_q=S, total_k=T,
            causal=causal, scale=scale,
        ),
        out_shape=jax.ShapeDtypeStruct((N, S, dk), q.dtype),
        grid_spec=pl.GridSpec(
            grid=(N, pl.cdiv(S, bq), pl.cdiv(T, bk)),
            in_specs=[
                pl.BlockSpec((1, bq, dk), lambda n, i, j: (n, i, 0)),
                pl.BlockSpec((1, bk, dk), lambda n, i, j: (n, j, 0)),
                pl.BlockSpec((1, bq, dk), lambda n, i, j: (n, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda n, i, j: (n, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda n, i, j: (n, i, 0)),
                pl.BlockSpec((1, bk, dk), lambda n, i, j: (n, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, dk), lambda n, i, j: (n, i, 0)),
            scratch_shapes=[pltpu.VMEM((bq, dk), jnp.float32)],
        ),
        name=f"ff_flash_bwd_dq{_variant(causal)}",
        interpret=_interpret(),
    )(q, k, do, lse, delta, v)
    return dq, dkv[0], dkv[1]


# ---------------------------------------------------------------------------
# custom-VJP wrapper


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, do, causal, scale, block_q, block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jnp.ndarray,  # (B, S, H, dk)
    k: jnp.ndarray,  # (B, T, H, dk)
    v: jnp.ndarray,  # (B, T, H, dk)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    """Fused multi-head attention, differentiable. Heads must already be
    repeated for GQA (models/transformer.make_flash_attention does)."""
    B, S, H, dk = q.shape
    T = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, dk)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, dk)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, dk)
    out = _flash(qf, kf, vf, causal, scale, block_q, block_k)
    return out.reshape(B, H, S, dk).transpose(0, 2, 1, 3)

"""Blockwise flash attention (forward and backward) as Pallas TPU kernels.

Tiling: grid (B, H, nq, nk) — the k-block axis is innermost, so the TPU
sequential grid revisits the same output block while streaming k/v tiles
through VMEM.  Online softmax state (m, l) and the f32 accumulator live in
VMEM scratch; the output is written on the final k step.

Block shapes default to (128, head_dim) q-tiles and (128, head_dim)
kv-tiles: MXU-aligned (multiples of 128 on the matmul dims) and a VMEM
working set of ~(2*bq*Dh + 2*bk*Dh + bq*bk) * 4 B ~ 0.5 MB at Dh=128 —
comfortably inside the ~16 MB/core VMEM budget with double buffering.

Causal + sliding-window masking is applied inside the tile.  The grid is
not pruned: fully-masked k-tiles are still visited and their DMAs issued
(carving the grid per q row would recover the ~2x causal saving).

The per-row softmax statistics (``lse`` from the forward, ``delta`` in the
backward) are carried as ``(B, H, Sq, 1)`` arrays: the TPU lowering needs
the last two block dims to be multiples of (8, 128) or equal to the full
array dims, so a ``(block_q, 1)`` block is legal where ``(1, 1, block_q)``
over ``(B, H, Sq)`` is not.

Every entry point takes ``interpret`` explicitly: ``True`` runs the Pallas
interpreter (CPU), ``False`` compiles for the TPU.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int],
    q_offset: int, block_q: int, block_k: int, num_k_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, Dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, Dh)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, Dh)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window

    s = q @ k.T                                          # (bq, bk)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                  # (bq,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
    m_scr[...] = m_cur

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _flash_fwd_lse_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int],
    q_offset: int, block_q: int, block_k: int, num_k_blocks: int,
):
    """Forward that also emits logsumexp rows (needed by the backward)."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, q @ k.T, NEG_INF)
    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
    m_scr[...] = m_cur

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, None].astype(lse_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    dq_scr,
    *, scale: float, causal: bool, window: Optional[int],
    q_offset: int, block_q: int, block_k: int, num_k_blocks: int,
):
    """dq pass: grid (B, H, nq, nk); accumulate dq over k blocks."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)            # (bq, 1)
    delta = delta_ref[0, 0].astype(jnp.float32)        # (bq, 1)
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, (q * scale) @ k.T, NEG_INF)
    p = jnp.exp(s - lse)                               # softmax probs
    dp = do @ v.T                                      # (bq, bk)
    ds = p * (dp - delta)                              # (bq, bk)
    dq_scr[...] += (ds @ k) * scale

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale: float, causal: bool, window: Optional[int],
    q_offset: int, block_q: int, block_k: int, num_q_blocks: int,
):
    """dk/dv pass: grid (B, H, nk, nq); accumulate over q blocks."""
    ikb = pl.program_id(2)
    iqb = pl.program_id(3)

    @pl.when(iqb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)
    delta = delta_ref[0, 0].astype(jnp.float32)
    qpos = iqb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ikb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, (q * scale) @ k.T, NEG_INF)
    p = jnp.exp(s - lse)
    dv_scr[...] += p.T @ do
    dp = do @ v.T
    ds = p * (dp - delta)
    dk_scr[...] += (ds.T @ q) * scale

    @pl.when(iqb == num_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_fwd_lse(
    q, k, v, *, causal=True, window=None, scale=None, q_offset=0,
    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K, interpret: bool,
):
    B, H, Sq, Dh = q.shape
    Hk, Skv = k.shape[1], k.shape[2]
    group = H // Hk
    if scale is None:
        scale = Dh ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    nq, nk = Sq // block_q, Skv // block_k
    kernel = functools.partial(
        _flash_fwd_lse_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k, num_k_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        name="flash_fwd_lse",
        interpret=interpret,
    )(q, k, v)


def flash_attention_bwd(
    q, k, v, o, lse, do, *, causal=True, window=None, scale=None,
    q_offset=0, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
    interpret: bool,
):
    """Blocked backward (dq then dk/dv); GQA handled by summing dk/dv over
    the query-head group outside (kv heads are broadcast in the kernels)."""
    B, H, Sq, Dh = q.shape
    Hk, Skv = k.shape[1], k.shape[2]
    group = H // Hk
    if scale is None:
        scale = Dh ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    nq, nk = Sq // block_q, Skv // block_k
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1, keepdims=True
    )  # (B, H, Sq, 1)

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, causal=causal, window=window,
            q_offset=q_offset, block_q=block_q, block_k=block_k, num_k_blocks=nk,
        ),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, Dh), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, causal=causal, window=window,
            q_offset=q_offset, block_q=block_q, block_k=block_k, num_q_blocks=nq,
        ),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, ik, iq, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, ik, iq, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ik, iq: (b, h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Skv, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, Skv, Dh), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, Dh), jnp.float32),
            pltpu.VMEM((block_k, Dh), jnp.float32),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    # reduce over the GQA group back to kv heads
    dk = dk_h.reshape(B, Hk, group, Skv, Dh).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hk, group, Skv, Dh).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


def flash_attention_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool,
) -> jax.Array:
    """q: (B, H, Sq, Dh); k/v: (B, Hk, Skv, Dh) with H % Hk == 0."""
    B, H, Sq, Dh = q.shape
    Hk, Skv = k.shape[1], k.shape[2]
    group = H // Hk
    if scale is None:
        scale = Dh ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, block_q, Skv, block_k)
    nq, nk = Sq // block_q, Skv // block_k

    kernel = functools.partial(
        _flash_fwd_kernel,
        scale=scale, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)

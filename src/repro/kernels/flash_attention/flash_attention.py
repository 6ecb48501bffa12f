"""Blockwise flash attention (forward and backward) as Pallas TPU kernels.

Tiling: grid (B, H, nq, nk) — the k-block axis is innermost, so the TPU
sequential grid revisits the same output block while streaming k/v tiles
through VMEM.  The output is written on the final k step.

Forward.  One kernel body serves ``flash_attention_fwd`` and
``flash_attention_fwd_lse``; the second also writes the logsumexp rows.

* State layout: the online-softmax row statistics ``m`` and ``l`` live in
  ``(block_q, 128)`` f32 VMEM scratch, the same value in every lane, and
  the row max and row sum of each tile are taken with ``keepdims=True``.
  A tile's statistics never leave the 2-D (sublane, lane) layout, so no
  step pays a relayout to a 1-D vector and back.
* Pruning: ``kv_block_range`` gives, from ``causal``, ``window`` and the
  static ``q_offset``, the first and last K/V block that any row of a
  q-block can see.  The K/V index map clamps the k step into that range,
  so a step outside it maps to the block already in VMEM and issues no
  DMA, and ``pl.when`` skips its compute.  Tiles wholly inside the
  visible region skip the mask as well.  ``visited_share`` is the share
  of (q-block, k-block) tiles that run.
* Block sizes: unless the caller passes them, ``fwd_block_sizes`` picks
  them from the shape: of 1024/512/256/128, those that divide the
  length, the largest ``block_k`` and then ``block_q`` whose working set
  fits ``FWD_VMEM_BUDGET``; a sequence shorter than 128 is one block.

Backward.  Fixed (128, head_dim) q- and kv-tiles, every tile visited:
fully-masked k-tiles still run and their DMAs are issued.

Head widths.  Queries and keys share one width and values may have
another (multi-head latent attention: 192 and 128).  Every block, output
and scratch that holds values, outputs or their gradients (v, o, dO, dV,
``acc``) is taken at the value width of ``v``; q, k, dQ and dK at the
width of ``q``.

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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
LANES = 128
# VMEM the forward's working set may take: the default scoped VMEM limit
# of a TPU v5e core.  See ``_fwd_vmem_bytes``.
FWD_VMEM_BUDGET = 16 * 1024 * 1024
FWD_BLOCK_CANDIDATES = (1024, 512, 256, 128)
_NT = (((1,), (1,)), ((), ()))  # contract the last dims: q @ k.T


def kv_block_range(iq, *, causal, window, q_offset, block_q, block_k, kv_len):
    """First and last K/V block that any query row of q-block ``iq`` can
    see.  ``iq`` is a Python int or a traced int32; query row r sits at
    position ``q_offset + r`` of the kv timeline.  Every block in the
    range has at least one unmasked (query, key) pair when
    ``q_offset + Sq <= kv_len``."""
    nk = kv_len // block_k
    first_q = q_offset + iq * block_q
    lo = 0
    hi = nk - 1
    if window is not None:
        lo = jnp.clip((first_q - window + 1) // block_k, 0, nk - 1)
    if causal:
        hi = jnp.clip((first_q + block_q - 1) // block_k, 0, nk - 1)
    return lo, hi


def visited_share(*, q_len, kv_len, causal, window, q_offset, block_q, block_k) -> float:
    """Share of the forward's (q-block, k-block) tiles that run: the sum
    over q-blocks of the K/V blocks in ``kv_block_range``, over nq * nk."""
    nq, nk = q_len // block_q, kv_len // block_k
    visited = 0
    for iq in range(nq):
        lo, hi = kv_block_range(
            iq, causal=causal, window=window, q_offset=q_offset,
            block_q=block_q, block_k=block_k, kv_len=kv_len,
        )
        visited += int(hi) - int(lo) + 1
    return visited / (nq * nk)


def _fwd_vmem_bytes(block_q: int, block_k: int, head_dim: int, itemsize: int,
                    v_dim: Optional[int] = None) -> int:
    """VMEM of one forward grid step: double-buffered q, k, v, o and lse
    blocks (lse padded to 128 lanes), the m/l/acc scratch, the f32 copies
    of q, k, v, and two f32 (block_q, block_k) tiles (scores and
    probabilities).  ``v_dim`` is the value width (default ``head_dim``)."""
    dv = head_dim if v_dim is None else v_dim
    qk = (block_q + block_k) * head_dim
    vo = (block_k + block_q) * dv
    blocks = 2 * ((qk + vo) * itemsize + block_q * LANES * 4)
    scratch = (2 * LANES + dv) * block_q * 4
    tiles = (qk + block_k * dv) * 4 + 2 * block_q * block_k * 4
    return blocks + scratch + tiles


def fwd_block_sizes(q_len: int, kv_len: int, head_dim: int, itemsize: int,
                    v_dim: Optional[int] = None):
    """(block_q, block_k) of the forward: of the candidates that divide
    each length, the largest ``block_k`` and then the largest ``block_q``
    whose working set fits ``FWD_VMEM_BUDGET``.  A larger ``block_k``
    spreads each step's rescale of the softmax state and of ``acc`` over
    more keys.  On one TPU v5e, causal at (B 2, H 32, S 4,096, Dh 128),
    bf16, a call took 3.0 ms at 1024 x 1024, 3.5 ms at 512 x 1024, 5.2 ms
    at 512 x 512 and 25 ms at 128 x 128."""

    def divisors(n):
        return [c for c in FWD_BLOCK_CANDIDATES if n % c == 0] or [min(DEFAULT_BLOCK_Q, n)]

    for bk in divisors(kv_len):
        for bq in divisors(q_len):
            if _fwd_vmem_bytes(bq, bk, head_dim, itemsize, v_dim) <= FWD_VMEM_BUDGET:
                return bq, bk
    return min(DEFAULT_BLOCK_Q, q_len), min(DEFAULT_BLOCK_K, kv_len)


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, *refs,
    scale: float, causal: bool, window: Optional[int],
    q_offset: int, block_q: int, block_k: int, kv_len: int,
):
    """Forward; ``refs`` is ``(lse_ref, m_scr, l_scr, acc_scr)`` when the
    logsumexp rows are written, else ``(m_scr, l_scr, acc_scr)``."""
    lse_ref = refs[0] if len(refs) == 4 else None
    m_scr, l_scr, acc_scr = refs[-3:]
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = kv_len // block_k

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, Dh)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, Dh)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, Dv)
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        if masked:
            shape = (block_q, block_k)
            qpos = iq * block_q + q_offset + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            mask = jnp.ones(shape, bool)
            if causal:
                mask &= kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                  # (bq, 128)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)                     # (bq, 128)
        p = jnp.exp(s - m_next[:, :1])                       # (bq, bk)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + p @ v
        m_scr[...] = m_next

    if not causal and window is None:
        tile(masked=False)
    else:
        lo, hi = kv_block_range(
            iq, causal=causal, window=window, q_offset=q_offset,
            block_q=block_q, block_k=block_k, kv_len=kv_len,
        )
        first_q = q_offset + iq * block_q
        first_k = ik * block_k
        unmasked = True
        if causal:
            unmasked &= first_k + block_k - 1 <= first_q
        if window is not None:
            unmasked &= first_k > first_q + block_q - 1 - window
        visible = (lo <= ik) & (ik <= hi)
        pl.when(visible & unmasked)(functools.partial(tile, masked=False))
        pl.when(visible & jnp.logical_not(unmasked))(functools.partial(tile, masked=True))

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, :1], 1e-30)           # (bq, 1)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = (m_scr[...][:, :1] + jnp.log(l)).astype(lse_ref.dtype)


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


def flash_attention_bwd(
    q, k, v, o, lse, do, *, causal=True, window=None, scale=None,
    q_offset=0, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
    interpret: bool,
):
    """Blocked backward (dq then dk/dv); GQA handled by summing dk/dv over
    the query-head group outside (kv heads are broadcast in the kernels)."""
    B, H, Sq, Dh = q.shape
    Hk, Skv, Dv = k.shape[1], k.shape[2], v.shape[3]
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
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
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
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, ik, iq, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ik, iq: (b, h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Skv, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, Skv, Dv), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, Dh), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    # reduce over the GQA group back to kv heads
    dk = dk_h.reshape(B, Hk, group, Skv, Dh).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hk, group, Skv, Dv).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


def _flash_forward(
    q, k, v, *, with_lse: bool, causal, window, scale, q_offset,
    block_q, block_k, interpret: bool,
):
    B, H, Sq, Dh = q.shape
    Hk, Skv, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // Hk
    if scale is None:
        scale = Dh ** -0.5
    if block_q is None or block_k is None:
        auto_q, auto_k = fwd_block_sizes(Sq, Skv, Dh, q.dtype.itemsize, Dv)
        block_q = auto_q if block_q is None else block_q
        block_k = auto_k if block_k is None else block_k
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, block_q, Skv, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    kv_range = functools.partial(
        kv_block_range, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, kv_len=Skv,
    )

    def kv_map(b, h, iq, ik):
        # A step outside the visible range maps to a block in range, the one
        # already in VMEM, so Pallas fetches nothing for it.
        lo, hi = kv_range(iq)
        return b, h // group, jnp.clip(ik, lo, hi), 0

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k, kv_len=Skv,
    )
    q_spec = pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, iq, ik: (b, h, iq, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, Dh), kv_map)
    v_spec = pl.BlockSpec((1, 1, block_k, Dv), kv_map)
    out_specs = [pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, iq, ik: (b, h, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        name="flash_fwd_lse" if with_lse else "flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return tuple(outs) if with_lse else outs[0]


def flash_attention_fwd_lse(
    q, k, v, *, causal=True, window=None, scale=None, q_offset=0,
    block_q=None, block_k=None, interpret: bool,
):
    """Forward that also returns the logsumexp rows (B, H, Sq, 1), f32,
    which the backward needs.  Block sizes default to ``fwd_block_sizes``."""
    return _flash_forward(
        q, k, v, with_lse=True, causal=causal, window=window, scale=scale,
        q_offset=q_offset, block_q=block_q, block_k=block_k, interpret=interpret,
    )


def flash_attention_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool,
) -> jax.Array:
    """q: (B, H, Sq, Dh); k: (B, Hk, Skv, Dh), v: (B, Hk, Skv, Dv) with
    H % Hk == 0 -> (B, H, Sq, Dv).  Block sizes default to
    ``fwd_block_sizes``."""
    return _flash_forward(
        q, k, v, with_lse=False, causal=causal, window=window, scale=scale,
        q_offset=q_offset, block_q=block_q, block_k=block_k, interpret=interpret,
    )

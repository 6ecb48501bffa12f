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
  fits ``VMEM_BUDGET``; a sequence shorter than 128 is one block.

Backward.  Two kernels, pruned as the forward is, with f32 operands into
the MXU and f32 accumulation.

* ``flash_bwd_dq``, grid (B, H, nq, nk): dq of one q-block accumulates
  over the K/V blocks in ``kv_block_range``; the K/V index maps clamp
  into that range.
* ``flash_bwd_dkv``, grid (B, H, nk, nq): dk and dv of one k-block
  accumulate over the q-blocks in ``q_block_range``, the mirror range:
  the first and last q-block with a row that sees a key of the block.
  The index maps of q, dO, lse and delta clamp into it.  A k-block that
  no query sees (keys past the last query, or before the first query's
  window) runs no tile and writes zeros.
* Block sizes: unless the caller passes them, ``bwd_block_sizes`` gives
  each kernel its own, from the shape: dq a tall q-block that streams k,
  dk/dv a tall k-block that streams q.  ``visited_share(kernel=...)`` is
  the share of tiles each kernel runs.

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
# VMEM a kernel's working set may take: the default scoped VMEM limit of a
# TPU v5e core.  See ``_fwd_vmem_bytes`` and ``_bwd_vmem_bytes``.
VMEM_BUDGET = 16 * 1024 * 1024
BLOCK_CANDIDATES = (1024, 512, 256, 128)
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


def q_block_range(ik, *, causal, window, q_offset, block_q, block_k, q_len):
    """First and last q-block with a query row that can see a key of
    k-block ``ik``: the mirror of ``kv_block_range``.  Key j is seen by the
    rows at positions ``j`` (causal) to ``j + window - 1`` (window), so
    every block in the range has at least one unmasked (query, key) pair.
    Where no query sees the block (keys past the last query, or before the
    first query's window) the range is empty: ``lo > hi``, with ``lo`` in
    [0, nq] and ``hi`` in [-1, nq - 1]."""
    nq = q_len // block_q
    first_k = ik * block_k
    lo = 0
    hi = nq - 1
    if causal:
        lo = jnp.clip((first_k - q_offset) // block_q, 0, nq)
    if window is not None:
        hi = jnp.clip((first_k + block_k - 1 + window - 1 - q_offset) // block_q, -1, nq - 1)
    return lo, hi


def visited_share(*, q_len, kv_len, causal, window, q_offset, block_q, block_k,
                  kernel="fwd") -> float:
    """Share of a kernel's (q-block, k-block) tiles that run, over nq * nk.
    The forward and the dq kernel (``kernel`` "fwd" or "dq") run, for each
    q-block, the K/V blocks in ``kv_block_range``; the dk/dv kernel ("dkv")
    runs, for each k-block, the q-blocks in ``q_block_range``."""
    nq, nk = q_len // block_q, kv_len // block_k
    mask_kw = dict(causal=causal, window=window, q_offset=q_offset,
                   block_q=block_q, block_k=block_k)
    visited = 0
    if kernel == "dkv":
        for ik in range(nk):
            lo, hi = q_block_range(ik, q_len=q_len, **mask_kw)
            visited += max(int(hi) - int(lo) + 1, 0)
    else:
        for iq in range(nq):
            lo, hi = kv_block_range(iq, kv_len=kv_len, **mask_kw)
            visited += int(hi) - int(lo) + 1
    return visited / (nq * nk)


def _divisors(n):
    """The block candidates that divide ``n``, largest first; a length
    shorter than 128 is one block."""
    return [c for c in BLOCK_CANDIDATES if n % c == 0] or [min(DEFAULT_BLOCK_Q, n)]


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
    whose working set fits ``VMEM_BUDGET``.  A larger ``block_k``
    spreads each step's rescale of the softmax state and of ``acc`` over
    more keys.  On one TPU v5e, causal at (B 2, H 32, S 4,096, Dh 128),
    bf16, a call took 3.0 ms at 1024 x 1024, 3.5 ms at 512 x 1024, 5.2 ms
    at 512 x 512 and 25 ms at 128 x 128."""
    for bk in _divisors(kv_len):
        for bq in _divisors(q_len):
            if _fwd_vmem_bytes(bq, bk, head_dim, itemsize, v_dim) <= VMEM_BUDGET:
                return bq, bk
    return min(DEFAULT_BLOCK_Q, q_len), min(DEFAULT_BLOCK_K, kv_len)


def _bwd_vmem_bytes(block_q: int, block_k: int, head_dim: int, itemsize: int,
                    v_dim: Optional[int] = None, *, kernel: str) -> int:
    """VMEM of one grid step of a backward kernel (``kernel`` "dq" or
    "dkv"): double-buffered q, k, v, dO, lse and delta blocks (lse and
    delta padded to 128 lanes) and output blocks (dq; or dk and dv), the
    f32 accumulators of the outputs, the f32 copies of q, k, v and dO, and
    four f32 (block_q, block_k) tiles (s, p, dp, ds)."""
    dv = head_dim if v_dim is None else v_dim
    ins = (block_q + block_k) * (head_dim + dv)          # q, dO; k, v
    outs = block_q * head_dim if kernel == "dq" else block_k * (head_dim + dv)
    blocks = 2 * ((ins + outs) * itemsize + 2 * block_q * LANES * 4)
    return blocks + outs * 4 + ins * 4 + 4 * block_q * block_k * 4


def bwd_block_sizes(q_len: int, kv_len: int, head_dim: int, itemsize: int,
                    v_dim: Optional[int] = None):
    """((block_q, block_k) of the dq kernel, (block_q, block_k) of the
    dk/dv kernel).  Each kernel keeps its accumulator over the rows of one
    block and streams the other axis: dq takes the largest ``block_q`` and
    then the largest ``block_k``, dk/dv the largest ``block_k`` and then
    the largest ``block_q``, of the candidates that divide each length and
    whose working set fits ``VMEM_BUDGET``.  On one TPU v5e, causal, bf16,
    a call took, at (B 2, H 32, S 4,096, Dh 128, KV group 4): dq 5.0 ms at
    1024 x 512 (5.1 at 512 x 1024, 5.5 at 512 x 512, 20.0 at 128 x 128),
    dk/dv 6.2 ms at 512 x 1024 (6.4 at 1024 x 512, 6.6 at 512 x 512, 26.9
    at 128 x 128); at (B 2, H 16, S 8,192, q/k 192, v 128): dq 12.2 ms
    (12.6, 13.0, 50.3), dk/dv 13.4 ms (13.6, 14.3, 61.7).  1024 x 1024,
    past the budget, saved at most 3%."""
    def fits(kernel, bq, bk):
        return _bwd_vmem_bytes(bq, bk, head_dim, itemsize, v_dim, kernel=kernel) <= VMEM_BUDGET

    least = min(DEFAULT_BLOCK_Q, q_len), min(DEFAULT_BLOCK_K, kv_len)
    dq = next(((bq, bk) for bq in _divisors(q_len) for bk in _divisors(kv_len)
               if fits("dq", bq, bk)), least)
    dkv = next(((bq, bk) for bk in _divisors(kv_len) for bq in _divisors(q_len)
                if fits("dkv", bq, bk)), least)
    return dq, dkv


def _run_visible(tile, iq, ik, visible, *, causal, window, q_offset, block_q, block_k):
    """Run ``tile(mask)`` on tile (``iq``, ``ik``) where ``visible``: with
    ``mask`` None where every (query, key) pair of the tile is visible, so
    the mask is neither built nor applied, else with the tile's mask."""
    if not causal and window is None:
        tile(None)
        return
    first_q = q_offset + iq * block_q
    first_k = ik * block_k
    unmasked = True
    if causal:
        unmasked &= first_k + block_k - 1 <= first_q
    if window is not None:
        unmasked &= first_k > first_q + block_q - 1 - window
    pl.when(visible & unmasked)(lambda: tile(None))

    @pl.when(visible & jnp.logical_not(unmasked))
    def _masked():
        shape = (block_q, block_k)
        qpos = first_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        kpos = first_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = jnp.ones(shape, bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        tile(mask)


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

    def tile(mask):
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, Dh)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, Dh)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, Dv)
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                  # (bq, 128)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)                     # (bq, 128)
        p = jnp.exp(s - m_next[:, :1])                       # (bq, bk)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + p @ v
        m_scr[...] = m_next

    lo, hi = kv_block_range(
        iq, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
    )
    _run_visible(
        tile, iq, ik, (lo <= ik) & (ik <= hi), causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
    )

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
    q_offset: int, block_q: int, block_k: int, kv_len: int,
):
    """dq pass: grid (B, H, nq, nk); accumulate dq over the visible k blocks."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def tile(mask):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].astype(jnp.float32)            # (bq, 1)
        delta = delta_ref[0, 0].astype(jnp.float32)        # (bq, 1)
        s = (q * scale) @ k.T
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                               # softmax probs
        dp = do @ v.T                                      # (bq, bk)
        ds = p * (dp - delta)                              # (bq, bk)
        dq_scr[...] += (ds @ k) * scale

    lo, hi = kv_block_range(
        iq, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
    )
    _run_visible(
        tile, iq, ik, (lo <= ik) & (ik <= hi), causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
    )

    @pl.when(ik == kv_len // block_k - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale: float, causal: bool, window: Optional[int],
    q_offset: int, block_q: int, block_k: int, q_len: int,
):
    """dk/dv pass: grid (B, H, nk, nq); accumulate over the q blocks that
    see k-block ``ik``.  A k-block no query sees runs no tile and writes
    zeros."""
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(mask):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].astype(jnp.float32)
        delta = delta_ref[0, 0].astype(jnp.float32)
        s = (q * scale) @ k.T
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_scr[...] += p.T @ do
        dp = do @ v.T
        ds = p * (dp - delta)
        dk_scr[...] += (ds.T @ q) * scale

    lo, hi = q_block_range(
        ik, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, q_len=q_len,
    )
    _run_visible(
        tile, iq, ik, (lo <= iq) & (iq <= hi), causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
    )

    @pl.when(iq == q_len // block_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q, k, v, o, lse, do, *, causal=True, window=None, scale=None,
    q_offset=0, block_q=None, block_k=None, interpret: bool,
):
    """Blocked backward (dq then dk/dv); GQA handled by summing dk/dv over
    the query-head group outside (kv heads are broadcast in the kernels).
    Each kernel's blocks default to ``bwd_block_sizes``; explicit blocks
    serve both kernels."""
    B, H, Sq, Dh = q.shape
    Hk, Skv, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // Hk
    if scale is None:
        scale = Dh ** -0.5
    auto = bwd_block_sizes(Sq, Skv, Dh, q.dtype.itemsize, Dv)
    (dq_bq, dq_bk), (dkv_bq, dkv_bk) = (
        (min(block_q or bq, Sq), min(block_k or bk, Skv)) for bq, bk in auto
    )
    assert Sq % dq_bq == 0 and Skv % dq_bk == 0, (Sq, dq_bq, Skv, dq_bk)
    assert Sq % dkv_bq == 0 and Skv % dkv_bk == 0, (Sq, dkv_bq, Skv, dkv_bk)
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1, keepdims=True
    )  # (B, H, Sq, 1)
    mask_kw = dict(causal=causal, window=window, q_offset=q_offset)

    # A step outside the visible range maps to a block in range, the one
    # already in VMEM, so Pallas fetches nothing for it.
    def kv_map(b, h, iq, ik):
        lo, hi = kv_block_range(iq, block_q=dq_bq, block_k=dq_bk, kv_len=Skv, **mask_kw)
        return b, h // group, jnp.clip(ik, lo, hi), 0

    def q_map(b, h, ik, iq):
        lo, hi = q_block_range(ik, block_q=dkv_bq, block_k=dkv_bk, q_len=Sq, **mask_kw)
        return b, h, jnp.clip(jnp.clip(iq, lo, hi), 0, Sq // dkv_bq - 1), 0

    rows_q = lambda b, h, iq, ik: (b, h, iq, 0)
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, block_q=dq_bq, block_k=dq_bk,
            kv_len=Skv, **mask_kw,
        ),
        grid=(B, H, Sq // dq_bq, Skv // dq_bk),
        in_specs=[
            pl.BlockSpec((1, 1, dq_bq, Dh), rows_q),
            pl.BlockSpec((1, 1, dq_bk, Dh), kv_map),
            pl.BlockSpec((1, 1, dq_bk, Dv), kv_map),
            pl.BlockSpec((1, 1, dq_bq, Dv), rows_q),
            pl.BlockSpec((1, 1, dq_bq, 1), rows_q),
            pl.BlockSpec((1, 1, dq_bq, 1), rows_q),
        ],
        out_specs=pl.BlockSpec((1, 1, dq_bq, Dh), rows_q),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((dq_bq, Dh), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    rows_k = lambda b, h, ik, iq, g=group: (b, h // g, ik, 0)
    out_k = lambda b, h, ik, iq: (b, h, ik, 0)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, block_q=dkv_bq, block_k=dkv_bk,
            q_len=Sq, **mask_kw,
        ),
        grid=(B, H, Skv // dkv_bk, Sq // dkv_bq),
        in_specs=[
            pl.BlockSpec((1, 1, dkv_bq, Dh), q_map),
            pl.BlockSpec((1, 1, dkv_bk, Dh), rows_k),
            pl.BlockSpec((1, 1, dkv_bk, Dv), rows_k),
            pl.BlockSpec((1, 1, dkv_bq, Dv), q_map),
            pl.BlockSpec((1, 1, dkv_bq, 1), q_map),
            pl.BlockSpec((1, 1, dkv_bq, 1), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, dkv_bk, Dh), out_k),
            pl.BlockSpec((1, 1, dkv_bk, Dv), out_k),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Skv, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, Skv, Dv), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((dkv_bk, Dh), jnp.float32),
            pltpu.VMEM((dkv_bk, Dv), jnp.float32),
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

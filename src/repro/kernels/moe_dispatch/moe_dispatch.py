"""Row gather and gated combine of the held-expert layer as Pallas TPU
kernels.

The held-expert layer (``models/moe.py:moe_ffn_held``) sorts the T x K
(token, expert) pairs of a layer by expert.  The pairs routed to the
experts a chip holds come first; their count ``held`` is known only at
run time, and every pair slot past it belongs to an expert that is not
held.  Both kernels take ``held`` as a scalar-prefetch operand and touch
no pair row past it, so their work follows the held pairs and not the
T x K slots of the buffer, which keeps its worst-case size (dropless).

* ``gather_rows`` (``name="moe_gather"``), grid over tiles of pair rows:
  ``out[i] = scale[i] * x[src[i]]`` for ``i < held``.  Rows of the last
  partial tile past ``held`` come out zero.  The index maps clamp every
  tile wholly past ``held`` onto the last held tile and ``pl.when`` skips
  its body, so such a tile issues no DMA and writes nothing: its rows
  hold whatever the buffer held.  Given ``dot_with``, the kernel also
  returns ``<x[src[i]], dot_with[i]>`` in f32 (zero past ``held``).
* ``combine_rows`` (``name="moe_combine"``), grid over tiles of tokens:
  ``out[t] = sum_k [pos[t, k] < held] gate[t, k] y[pos[t, k]]``, summed in
  f32 in k order and stored in ``y.dtype``.  Only held rows are fetched:
  a tile's held pairs are, in each held group, one run of consecutive
  rows (``group_runs``), so the kernel loops over those runs and not over
  the tile's T x K slots.  A slot that is not held adds 0 through a
  select, so a stale staging row never reaches the sum, NaN or not.

Row access.  A DMA of one row of a 2-D bf16 array is not aligned to the
TPU's (16, 128) tiling, so both kernels fetch rows from a
``(rows, D / 128, 128)`` view in HBM, where each row is one whole tile,
into a ``(tile, D / 128, 128)`` VMEM buffer, and reshape that to
``(tile, D)`` in VMEM.  ``gather_rows`` views its (T, D) source so (XLA
copies T x D elements).  ``combine_rows`` reads the pair buffer, which
only ``relayout_rows`` (``name="moe_relayout"``) views so, over the held
tiles alone.

Per-row scalars (``scale`` and the f32 dot) travel as ``(n, 1)`` arrays
in ``(tile, 1)`` blocks: the TPU lowering takes a block whose last two
dims are multiples of (8, 128) or equal to the array's.

Every entry point takes ``interpret`` explicitly: ``True`` runs the
Pallas interpreter (CPU), ``False`` compiles for the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# a tile of rows takes at most this many bytes of one buffer, and a
# combine tile's staging (top_k rows a token) at most STAGE_BYTES
ROW_TILE_BYTES = 1 << 20
STAGE_BYTES = 4 << 20


def row_tile(n: int, row_bytes: int, budget: int = ROW_TILE_BYTES) -> int:
    """The largest power of two from 512 down to 16 that divides ``n``
    and whose rows fit ``budget``; ``n`` itself where none does."""
    for t in (512, 256, 128, 64, 32, 16):
        if n % t == 0 and t * row_bytes <= budget:
            return t
    return n


def _last_tile(held, tile):
    """Index of the last tile that holds a row below ``held`` (0 when
    none does)."""
    return jnp.maximum((held + tile - 1) // tile - 1, 0)


def _tiles_view(a: jax.Array) -> jax.Array:
    """(n, D) -> (n, D / 128, 128): each row one whole tile."""
    n, d = a.shape
    return a.reshape(n, d // LANES, LANES)


# ---------------------------------------------------------------------------
# moe_gather
# ---------------------------------------------------------------------------


def _gather_kernel(held_ref, src_ref, x_hbm, *refs, tile, scaled, dotted):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    other_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    buf, sem = refs
    held = held_ref[0]
    start = pl.program_id(0) * tile

    def copy(r):
        return pltpu.make_async_copy(x_hbm.at[src_ref[start + r]], buf.at[r], sem)

    @pl.when(start < held)
    def _():
        n = jnp.minimum(held - start, tile)

        @pl.loop(0, n)
        def _(r):
            copy(r).start()

        @pl.loop(0, n)
        def _(r):
            copy(r).wait()

        rows = buf[...].reshape(out_ref.shape)
        keep = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < n
        if scaled:
            out = rows.astype(jnp.float32) * scale_ref[...]
        else:
            out = rows
        out_ref[...] = jnp.where(keep, out, 0).astype(out_ref.dtype)
        if dotted:
            prod = rows.astype(jnp.float32) * other_ref[...].astype(jnp.float32)
            dot_ref[...] = jnp.where(keep, jnp.sum(prod, axis=1, keepdims=True), 0.0)


def gather_rows(
    x: jax.Array,
    src: jax.Array,
    held: jax.Array,
    scale: Optional[jax.Array] = None,
    dot_with: Optional[jax.Array] = None,
    *,
    interpret: bool,
):
    """Rows ``x[src[i]]`` (scaled by ``scale[i]``, f32, where given) for
    ``i < held``, as an (n, D) array of ``x.dtype`` with n = len(src);
    rows past ``held`` are zero in its last partial tile and unwritten
    after it.  With ``dot_with`` (n, D), returns ``(rows, dots)`` with
    ``dots[i] = <x[src[i]], dot_with[i]>`` in f32, shape (n,)."""
    T, D = x.shape
    n = src.shape[0]
    tile = row_tile(n, D * x.dtype.itemsize)
    scaled, dotted = scale is not None, dot_with is not None

    def rows(i, held_ref, src_ref):
        return (jnp.minimum(i, _last_tile(held_ref[0], tile)), 0)

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    args = [_tiles_view(x)]
    if scaled:
        in_specs.append(pl.BlockSpec((tile, 1), rows))
        args.append(scale.astype(jnp.float32).reshape(n, 1))
    if dotted:
        in_specs.append(pl.BlockSpec((tile, D), rows))
        args.append(dot_with)
    out_specs = [pl.BlockSpec((tile, D), rows)]
    out_shape = [jax.ShapeDtypeStruct((n, D), x.dtype)]
    if dotted:
        out_specs.append(pl.BlockSpec((tile, 1), rows))
        out_shape.append(jax.ShapeDtypeStruct((n, 1), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_gather_kernel, tile=tile, scaled=scaled, dotted=dotted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tile,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tile, D // LANES, LANES), x.dtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_gather",
    )(jnp.reshape(held, (1,)).astype(jnp.int32), src.astype(jnp.int32), *args)
    if dotted:
        return outs[0], outs[1].reshape(n)
    return outs[0]


# ---------------------------------------------------------------------------
# moe_relayout
# ---------------------------------------------------------------------------


def _relayout_kernel(held_ref, y_ref, out_ref, *, tile):
    @pl.when(pl.program_id(0) * tile < held_ref[0])
    def _():
        out_ref[...] = y_ref[...].reshape(out_ref.shape)


def relayout_rows(y: jax.Array, held: jax.Array, *, interpret: bool) -> jax.Array:
    """(n, D) -> (n, D / 128, 128) over the tiles that hold a row below
    ``held``; later tiles are not read and not written."""
    n, D = y.shape
    tile = row_tile(n, D * y.dtype.itemsize)

    def rows(i, held_ref):
        return (jnp.minimum(i, _last_tile(held_ref[0], tile)), 0)

    def rows3(i, held_ref):
        return (jnp.minimum(i, _last_tile(held_ref[0], tile)), 0, 0)

    return pl.pallas_call(
        functools.partial(_relayout_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tile,),
            in_specs=[pl.BlockSpec((tile, D), rows)],
            out_specs=pl.BlockSpec((tile, D // LANES, LANES), rows3),
        ),
        out_shape=jax.ShapeDtypeStruct((n, D // LANES, LANES), y.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_relayout",
    )(jnp.reshape(held, (1,)).astype(jnp.int32), y)


# ---------------------------------------------------------------------------
# moe_combine
# ---------------------------------------------------------------------------


def _combine_kernel(held_ref, order_s, runs_s, y_hbm, pos_ref, *refs, tile, k, groups, gated):
    refs = list(refs)
    gate_ref = refs.pop(0) if gated else None
    out_ref, buf, sem = refs
    held = held_ref[0]
    i = pl.program_id(0)
    nt = pl.num_programs(0)
    first = i * tile * k

    def copy(r):
        slot = order_s[r] - first
        return pltpu.make_async_copy(y_hbm.at[r], buf.at[slot % k, slot // k], sem)

    # the held pairs of this tile's tokens: in each held group, one run of
    # consecutive pair rows (a group's pairs keep their token order)
    for wait in (False, True):
        for g in range(groups):
            @pl.loop(runs_s[g * (nt + 1) + i], runs_s[g * (nt + 1) + i + 1])
            def _(r):
                if wait:
                    copy(r).wait()
                else:
                    copy(r).start()

    pos = pos_ref[...]
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for j in range(k):
        rows = buf[j].reshape(out_ref.shape).astype(jnp.float32)
        term = gate_ref[:, j:j + 1] * rows if gated else rows
        acc = acc + jnp.where(pos[:, j:j + 1] < held, term, 0.0)
    out_ref[...] = acc.astype(out_ref.dtype)


def group_runs(order: jax.Array, sizes: jax.Array, tile_slots: int) -> jax.Array:
    """(groups, n / tile_slots + 1): entry [g, i] is the first pair row of
    group g whose flat (token * K + slot) index is at least
    ``i * tile_slots``, for pairs sorted stably by group (``order`` holds
    each row's flat index; ``sizes`` the held groups' sizes, the pairs
    past them forming one last group).  Rows [g, i] to [g, i + 1] are
    group g's pairs of the tokens of tile i."""
    n, groups = order.shape[0], sizes.shape[0]
    if (groups + 1) * n >= 2**31:
        raise ValueError(f"{groups} groups over {n} pairs overflow int32 keys")
    group_of = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(n), side="right")
    key = group_of * n + order                      # ascending: by group, then flat index
    starts = jnp.arange(groups)[:, None] * n + jnp.arange(0, n + 1, tile_slots)[None, :]
    return jnp.searchsorted(key, starts, side="left").astype(jnp.int32)


def combine_rows(
    y3: jax.Array,
    order: jax.Array,
    pos: jax.Array,
    sizes: jax.Array,
    gates: Optional[jax.Array] = None,
    *,
    interpret: bool,
) -> jax.Array:
    """``out[t] = sum_k [pos[t, k] < held] gates[t, k] y[pos[t, k]]`` (unit
    gates where ``gates`` is None), held = sum(sizes), for pair rows ``y3``
    in the view of ``relayout_rows``, (n, D / 128, 128), sorted stably by
    group: ``order`` (n,) the flat index of each row, ``pos`` (T, K) its
    inverse, ``sizes`` the held groups' sizes; gates (T, K) f32.  Returns
    (T, D) in ``y3.dtype``."""
    n, c, _ = y3.shape
    T, K = pos.shape
    D = c * LANES
    tile = row_tile(T, K * D * y3.dtype.itemsize, STAGE_BYTES)
    runs = group_runs(order, sizes, tile * K)

    def toks(i, *_):
        return (i, 0)

    in_specs = [pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec((tile, K), toks)]
    args = [y3, pos.astype(jnp.int32)]
    if gates is not None:
        in_specs.append(pl.BlockSpec((tile, K), toks))
        args.append(gates.astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_combine_kernel, tile=tile, k=K, groups=sizes.shape[0],
                          gated=gates is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(T // tile,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, D), toks),
            scratch_shapes=[pltpu.VMEM((K, tile, c, LANES), y3.dtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((T, D), y3.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_combine",
    )(jnp.sum(sizes).reshape(1).astype(jnp.int32), order.astype(jnp.int32),
      runs.reshape(-1), *args)

"""Pure-jnp oracle for the held-expert layer's row gather and combine.

Semantics and arguments as ``ops.py``'s: pairs sorted stably by expert,
``order`` the flat (token * K + slot) index of each, ``pos`` (T, K) its
inverse, ``sizes`` the held experts' pair counts.  Nothing past the held
pairs is read; ``gather_ref`` returns zero rows there, where the kernel
leaves whole tiles unwritten.
"""

from __future__ import annotations

import jax.numpy as jnp


def gather_ref(x, order, pos, sizes, scale=None, dot_with=None):
    """Row i is ``scale[i] * x[order[i] // K]`` (f32 product, stored in
    ``x.dtype``) for the held pairs i < sum(sizes), zero after; with
    ``dot_with``, also the f32 dots ``<x[order[i] // K], dot_with[i]>``
    (zero past the held pairs)."""
    rows = x[order // pos.shape[1]]
    keep = (jnp.arange(order.shape[0]) < jnp.sum(sizes))[:, None]
    out = rows if scale is None else rows.astype(jnp.float32) * scale[:, None]
    out = jnp.where(keep, out, 0).astype(x.dtype)
    if dot_with is None:
        return out
    dots = jnp.sum(rows.astype(jnp.float32) * dot_with.astype(jnp.float32), axis=1)
    return out, jnp.where(keep[:, 0], dots, 0.0)


def combine_ref(y, order, pos, sizes, gates=None):
    """``out[t] = sum_k [pos[t, k] < held] gates[t, k] y[pos[t, k]]`` in
    f32 (unit gates where ``gates`` is None), held = sum(sizes), stored in
    ``y.dtype``."""
    rows = y[pos].astype(jnp.float32)                      # (T, K, D)
    terms = rows if gates is None else rows * gates[..., None]
    held = (pos < jnp.sum(sizes))[..., None]
    return jnp.sum(jnp.where(held, terms, 0.0), axis=1).astype(y.dtype)

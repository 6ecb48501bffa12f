"""Seeded values made on the device: weights from a layout, and keys.

A layout is a nested dict whose leaves are ``Leaf(shape, fan_in)``: a
truncated normal (±2σ) scaled by ``fan_in ** -0.5``, or ones where
``fan_in`` is None.  ``make`` turns a layout into arrays of one dtype in a
single jitted call, so the benchmark and the references get the same
numbers from the same seed without either taking them from the other.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    shape: Tuple[int, ...]
    fan_in: Optional[int]


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def root_key(seed: int, stream: int) -> jax.Array:
    """A key for one stream of values (weights, data, cache) from a seed of
    any size: the low and high 32 bits are folded in separately, so seeds
    above 2**32 stay distinct without 64-bit mode."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


WEIGHTS, DATA, CACHE = 1, 2, 3


def _values(layout, key, dtype):
    leaves, tdef = jax.tree_util.tree_flatten(layout, is_leaf=is_leaf)
    out = []
    for i, leaf in enumerate(leaves):
        if leaf.fan_in is None:
            out.append(jnp.ones(leaf.shape, dtype))
        else:
            x = jax.random.truncated_normal(
                jax.random.fold_in(key, i), -2.0, 2.0, leaf.shape, jnp.float32)
            out.append((x * leaf.fan_in ** -0.5).astype(dtype))
    return jax.tree_util.tree_unflatten(tdef, out)


def make(layout, seed: int, dtype, out_shardings: Any = None):
    """All weights of ``layout`` for ``seed`` in ``dtype``, made on the
    device in one jitted call (placed per ``out_shardings`` when given)."""
    fn = jax.jit(lambda key: _values(layout, key, dtype), out_shardings=out_shardings)
    return fn(root_key(seed, WEIGHTS))


def values_in(layout, key, dtype):
    """The same values as ``make`` for a key from ``root_key(seed,
    WEIGHTS)``, for use inside another jitted function."""
    return _values(layout, key, dtype)


def shapes(layout):
    return jax.tree_util.tree_map(lambda l: tuple(l.shape), layout, is_leaf=is_leaf)

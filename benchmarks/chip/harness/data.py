"""The traffic generator: inputs made on the device from ``--seed`` by the
parameters of a traffic mix.  The same seed gives the same inputs; rows,
layers and K/V each draw from a key of their own, so a subset can be made
again without the rest."""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from .weights import CACHE, DATA, root_key


def train_batches(seed: int, count: int, batch: int, seq: int, vocab: int,
                  sharding: Any = None) -> List[Dict[str, jax.Array]]:
    """``count`` batches of uniformly drawn ids: tokens (batch, seq) and
    their next-token targets, in one jitted call."""

    def make(key):
        out = []
        for i in range(count):
            ids = jax.random.randint(jax.random.fold_in(key, i), (batch, seq + 1), 0, vocab, jnp.int32)
            out.append({"tokens": ids[:, :-1], "targets": ids[:, 1:]})
        return out

    shard = None if sharding is None else [sharding] * count
    return jax.jit(make, out_shardings=shard)(root_key(seed, DATA))


def cache_prefix(key, layers: int, rows, filled: int, kv_heads: int, head_dim: int, dtype):
    """K and V of positions [0, filled) of every layer for the given rows
    (an int array): (layers, len(rows), filled, kv_heads, head_dim) each,
    standard normal (the RMS of a K after its RMSNorm), taken as already
    rotated.  ``key`` is ``root_key(seed, CACHE)``; each (layer, row, K or
    V) draws from its own key."""

    def block(layer, row, which):
        k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, layer), row), which)
        return jax.random.normal(k, (filled, kv_heads, head_dim), jnp.float32).astype(dtype)

    per_row = jax.vmap(block, in_axes=(None, 0, None))
    per_layer = jax.vmap(per_row, in_axes=(0, None, None))
    ls = jnp.arange(layers)
    return per_layer(ls, rows, 0), per_layer(ls, rows, 1)


def filled_cache(seed: int, layers: int, batch: int, capacity: int, filled: int,
                 kv_heads: int, head_dim: int, dtype, sharding: Any = None) -> Dict[str, jax.Array]:
    """A decode cache (the program's layout) whose first ``filled``
    positions hold ``cache_prefix`` and whose index is ``filled``."""

    def make(key):
        k, v = cache_prefix(key, layers, jnp.arange(batch), filled, kv_heads, head_dim, dtype)
        pad = ((0, 0), (0, 0), (0, capacity - filled), (0, 0), (0, 0))
        return {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad), "index": jnp.int32(filled)}

    return jax.jit(make, out_shardings=sharding)(root_key(seed, CACHE))


def first_tokens(seed: int, batch: int, vocab: int, sharding: Any = None) -> jax.Array:
    key = jax.random.fold_in(root_key(seed, DATA), 1 << 20)
    return jax.jit(lambda k: jax.random.randint(k, (batch, 1), 0, vocab, jnp.int32),
                   out_shardings=sharding)(key)

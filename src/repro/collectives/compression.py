"""Gradient compression for slow-axis reduction (beyond-paper optimization).

RailX reduces inter-node *bytes* topologically; on the slowest axis (cross-
pod) we additionally compress gradients before the inter-node phase of the
hierarchical schedule:

* ``int8_compress``/``int8_decompress`` — per-chunk symmetric int8 with
  fp32 scale (16.1 GB -> 4 GB for a 4B-param model update on the pod axis).
* ``ErrorFeedback`` — classical EF-SGD residual so compression error does
  not bias convergence (Karimireddy et al., 2019 style).
* ``compressed_hierarchical_all_reduce`` — RS(intra) -> int8 AR(inter) ->
  AG(intra), trading 4x inter bytes for quantization noise handled by EF.

These run inside shard_map like the plain schedules.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .schedules import AxisNames, all_gather_axis, reduce_scatter_axis


class Int8Compressed(NamedTuple):
    values: jax.Array   # int8
    scale: jax.Array    # f32 scalar per chunk


def int8_compress(x: jax.Array, chunk: int = 4096) -> Int8Compressed:
    """Symmetric per-chunk int8 quantization of a flat f32/bf16 array."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % chunk
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(-1, chunk).astype(jnp.float32)
    scale = jnp.max(jnp.abs(chunks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(chunks / scale), -127, 127).astype(jnp.int8)
    return Int8Compressed(q, scale)


def int8_decompress(c: Int8Compressed, shape: Tuple[int, ...], dtype) -> jax.Array:
    flat = (c.values.astype(jnp.float32) * c.scale).reshape(-1)
    n = 1
    for s in shape:
        n *= s
    return flat[:n].reshape(shape).astype(dtype)


class ErrorFeedback(NamedTuple):
    residual: jax.Array

    @staticmethod
    def init(shape, dtype=jnp.float32) -> "ErrorFeedback":
        return ErrorFeedback(jnp.zeros(shape, dtype))


def ef_compress(
    g: jax.Array, ef: ErrorFeedback, chunk: int = 4096
) -> Tuple[Int8Compressed, ErrorFeedback]:
    """Error-feedback int8: compress (g + residual), store new residual."""
    corrected = g.astype(jnp.float32) + ef.residual
    comp = int8_compress(corrected, chunk)
    approx = int8_decompress(comp, g.shape, jnp.float32)
    return comp, ErrorFeedback(corrected - approx)


def compressed_hierarchical_all_reduce(
    x: jax.Array,
    intra_axes: AxisNames,
    inter_axes: AxisNames,
    chunk: int = 4096,
) -> jax.Array:
    """Hierarchical AR with int8 payload on the inter phase.

    int8 partial sums overflow, so the inter phase uses the gather-reduce
    form (1-bit-Adam style): all-gather the int8 shards + scales across the
    inter axes, dequantize per-rank, and sum locally in f32.  Per-chip
    inter bytes drop ~8x versus an f32 all-reduce (all-gather moves
    (p-1)/p * V_int8 vs 2 (p-1)/p * V_f32); the gathered buffer is p x the
    shard, which is why this targets the small slow axis (pod).
    The payload appears as an ``s8`` all-gather in compiled HLO — the
    roofline collective parser credits the savings automatically.
    """
    orig_dtype = x.dtype
    shard = reduce_scatter_axis(x, intra_axes, dim=0)
    comp = int8_compress(shard, chunk)
    vals = all_gather_axis(comp.values[None], inter_axes, dim=0)   # (p, C, chunk) int8
    scales = all_gather_axis(comp.scale[None], inter_axes, dim=0)  # (p, C, 1) f32
    summed = jnp.sum(vals.astype(jnp.float32) * scales, axis=0)
    n = shard.size
    shard = summed.reshape(-1)[:n].reshape(shard.shape).astype(orig_dtype)
    return all_gather_axis(shard, intra_axes, dim=0)

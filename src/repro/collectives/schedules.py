"""Executable RailX collective schedules (paper §4.2) as shard_map programs.

These are the JAX counterparts of the paper's algorithms.  Inside a
``jax.shard_map`` region with mesh axes:

  * ``intra`` axes = the node's high-bandwidth 2D-mesh (k x bandwidth);
  * ``inter`` axes = rail rings across nodes (1 x bandwidth).

``hierarchical_all_reduce`` implements Eq. (8):
  phase 1  reduce-scatter over the intra axes (cheap, k x bandwidth)
  phase 2  all-reduce of the 1/|intra| shard over the inter axes
  phase 3  all-gather over the intra axes
Inter-node bytes drop from V to V/|intra| per chip versus a flat all-reduce
— exactly the paper's (2/k + 1/m) factor, and directly visible in compiled
HLO collective bytes (our roofline collective term).

``flat_all_reduce`` (baseline) and ``ring_all_reduce_2d`` (Eq. 7 flavor:
psum over both axes jointly) are provided for comparison, along with
``all_to_all_axis`` used by expert parallelism and ``reduce_scatter_axis`` /
``all_gather_axis`` building blocks used by FSDP.

All functions take/return *per-device local* arrays (shard_map semantics)
and are pure jax.lax — usable inside pjit/shard_map at any nesting.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisNames = Union[str, Tuple[str, ...]]


def _axes_tuple(axes: AxisNames) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _axis_size(axes: AxisNames) -> int:
    size = 1
    for a in _axes_tuple(axes):
        size *= jax.lax.axis_size(a)
    return size


# ---------------------------------------------------------------------------
# Building blocks (inside shard_map)
# ---------------------------------------------------------------------------


def reduce_scatter_axis(x: jax.Array, axes: AxisNames, dim: int = 0) -> jax.Array:
    """Reduce-scatter along (possibly several) mesh axes, tiled on ``dim``."""
    for a in _axes_tuple(axes):
        x = jax.lax.psum_scatter(x, a, scatter_dimension=dim, tiled=True)
    return x


def all_gather_axis(x: jax.Array, axes: AxisNames, dim: int = 0) -> jax.Array:
    for a in reversed(_axes_tuple(axes)):
        x = jax.lax.all_gather(x, a, axis=dim, tiled=True)
    return x


def all_reduce_axis(x: jax.Array, axes: AxisNames) -> jax.Array:
    return jax.lax.psum(x, _axes_tuple(axes))


def all_to_all_axis(
    x: jax.Array, axis: str, split_dim: int, concat_dim: int
) -> jax.Array:
    """EP dispatch/combine primitive: exchange equal splits along a mesh
    axis (paper Table 4 'All-to-All' row; rail-ring a2a carries this)."""
    return jax.lax.all_to_all(
        x, axis, split_axis=split_dim, concat_axis=concat_dim, tiled=True
    )


# ---------------------------------------------------------------------------
# All-reduce schedules (paper §4.2)
# ---------------------------------------------------------------------------


def flat_all_reduce(x: jax.Array, axes: AxisNames) -> jax.Array:
    """Baseline: single psum over all participating axes (XLA picks the
    schedule; inter-node bytes ~= V per chip)."""
    return all_reduce_axis(x, axes)


def hierarchical_all_reduce(
    x: jax.Array,
    intra_axes: AxisNames,
    inter_axes: AxisNames,
    scatter_dim: int = 0,
) -> jax.Array:
    """RailX hierarchical all-reduce (paper Eq. 8).

    Requires ``x.shape[scatter_dim]`` divisible by the intra axes' total
    size.  Phase 2's inter-node traffic is V/|intra| per chip.
    """
    x = reduce_scatter_axis(x, intra_axes, dim=scatter_dim)   # k x BW domain
    x = all_reduce_axis(x, inter_axes)                        # rails
    x = all_gather_axis(x, intra_axes, dim=scatter_dim)       # k x BW domain
    return x


def ring_all_reduce_2d(
    x: jax.Array,
    axes_xy: Tuple[str, str],
    scatter_dim: int = 0,
) -> jax.Array:
    """2D-ring schedule (paper Eq. 7): split data in two halves; half A is
    reduce-scattered along X then Y, half B along Y then X; then the
    mirrored all-gathers.  Models the X/Y simultaneous rings of [48, 98]."""
    ax, ay = axes_xy
    group = 2 * jax.lax.axis_size(ax) * jax.lax.axis_size(ay)
    x, pad = _pad_to_multiple(x, group, scatter_dim)
    n = x.shape[scatter_dim]
    half = n // 2
    a, b = jnp.split(x, [half], axis=scatter_dim)
    a = reduce_scatter_axis(a, (ax, ay), dim=scatter_dim)
    b = reduce_scatter_axis(b, (ay, ax), dim=scatter_dim)
    a = all_gather_axis(a, (ax, ay), dim=scatter_dim)
    b = all_gather_axis(b, (ay, ax), dim=scatter_dim)
    out = jnp.concatenate([a, b], axis=scatter_dim)
    if pad:
        out = jax.lax.slice_in_dim(out, 0, n - pad, axis=scatter_dim)
    return out


def hierarchical_reduce_scatter(
    x: jax.Array,
    intra_axes: AxisNames,
    inter_axes: AxisNames,
    dim: int = 0,
) -> jax.Array:
    """Gradient-sharding variant (FSDP): RS(intra) then RS(inter) — the
    output shard lives on the (intra x inter) product axis order."""
    x = reduce_scatter_axis(x, intra_axes, dim=dim)
    x = reduce_scatter_axis(x, inter_axes, dim=dim)
    return x


def hierarchical_all_gather(
    x: jax.Array,
    intra_axes: AxisNames,
    inter_axes: AxisNames,
    dim: int = 0,
) -> jax.Array:
    x = all_gather_axis(x, inter_axes, dim=dim)
    x = all_gather_axis(x, intra_axes, dim=dim)
    return x


# ---------------------------------------------------------------------------
# Whole-pytree gradient reduction (used by train_step)
# ---------------------------------------------------------------------------


def _pad_to_multiple(x: jax.Array, mult: int, dim: int) -> Tuple[jax.Array, int]:
    n = x.shape[dim]
    pad = (-n) % mult
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[dim] = (0, pad)
        x = jnp.pad(x, widths)
    return x, pad


def tree_hierarchical_all_reduce(
    grads,
    intra_axes: AxisNames,
    inter_axes: AxisNames,
):
    """Apply the hierarchical schedule leaf-wise (flattening each leaf so
    the scatter dim is always divisible; pads then unpads)."""
    intra = _axis_size(intra_axes)

    def red(g):
        shape = g.shape
        flat = g.reshape(-1)
        flat, pad = _pad_to_multiple(flat, intra, 0)
        flat = hierarchical_all_reduce(flat, intra_axes, inter_axes, 0)
        if pad:
            flat = flat[: flat.shape[0] - pad]
        return flat.reshape(shape)

    return jax.tree_util.tree_map(red, grads)


def tree_flat_all_reduce(grads, axes: AxisNames):
    return jax.tree_util.tree_map(lambda g: all_reduce_axis(g, axes), grads)


# ---------------------------------------------------------------------------
# Convenience: jit-able host-level wrappers (for tests/benchmarks)
# ---------------------------------------------------------------------------


def make_all_reduce_fn(
    mesh: Mesh,
    spec: P,
    schedule: str,
    intra_axes: AxisNames,
    inter_axes: AxisNames,
):
    """Build a jitted x -> all_reduce(x) over the mesh for testing and for
    HLO collective-byte measurement.  ``spec`` is the input sharding."""

    def body(x):
        if schedule == "hierarchical":
            return hierarchical_all_reduce(x, intra_axes, inter_axes)
        if schedule == "flat":
            return flat_all_reduce(x, _axes_tuple(intra_axes) + _axes_tuple(inter_axes))
        if schedule == "ring2d":
            ax = _axes_tuple(intra_axes) + _axes_tuple(inter_axes)
            assert len(ax) == 2
            return ring_all_reduce_2d(x, (ax[0], ax[1]))
        raise ValueError(schedule)

    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )
    return jax.jit(mapped)

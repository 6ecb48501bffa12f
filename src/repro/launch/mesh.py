"""Production mesh construction (assignment contract).

The single-pod mesh (16, 16) = ("data", "model") models one RailX
row-block: "model" = the 4x4-chip node 2D-mesh (TP domain, k x bandwidth),
"data" = 16 nodes joined by rail rings (FSDP/EP/DP domain).  The multi-pod
mesh (2, 16, 16) adds the "pod" axis = two RailX blocks joined by a
dimension-split rail group (slow DP domain).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """General mesh helper (tests / examples / heterogeneous topologies)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def railx_mesh_from_plan(plan) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """Translate a core.mapping.MappingResult dimension split into a mesh
    signature (sizes, names) — the launcher glue between the paper's
    topology plan and jax."""
    sizes = []
    names = []
    for spec in plan.specs:
        if spec.scale > 1:
            sizes.append(spec.scale)
            names.append(spec.name)
    return tuple(sizes), tuple(names)

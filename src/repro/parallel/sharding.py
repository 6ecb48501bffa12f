"""Logical-axis sharding rules (MaxText-style) for the RailX mesh mapping.

Model code annotates parameters and activations with *logical* axis names
("embed", "heads", "vocab", "expert", "batch", "seq", ...).  A
``ShardingRules`` table maps logical names to physical mesh axes; the RailX
mapping solver (core.mapping) decides that table per workload — TP on the
intra-node 2D-mesh ("model" axis), FSDP/EP/DP on the rail dimensions
("data", "pod").

Usage:
    rules = ShardingRules(DEFAULT_RULES)
    with use_rules(rules), mesh:
        y = shard_hint(x, ("batch", "seq", "embed"))

Outside any mesh/rules context ``shard_hint`` is a no-op so single-device
CPU tests run the exact same model code.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PhysAxes = Union[None, str, Tuple[str, ...]]


# logical axis -> physical mesh axes, for the production (data, model) mesh
# with optional leading pod axis.
DEFAULT_RULES: Dict[str, PhysAxes] = {
    # data-parallel batch: pod x rail rings (FSDP domain shares the batch)
    "batch": ("pod", "data"),
    "ep_batch": ("pod", "data"),   # batch groups that feed EP all-to-all
    # sequence left unsharded by default (CP optional)
    "seq": None,
    "kv_seq": None,
    # tensor parallelism on the intra-node 2D-mesh
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "tp_embed": "model",
    # FSDP parameter sharding over the rail (data) axis
    "fsdp": "data",
    # expert parallelism over the rail-ring all-to-all dimension
    "expert": "data",
    # never sharded
    "embed": None,
    "head_dim": None,
    "state": None,
    "stack": None,
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    table: Dict[str, PhysAxes]

    def spec(self, names: Sequence[Optional[str]]) -> P:
        phys = []
        used = set()
        for nm in names:
            if nm is None:
                phys.append(None)
                continue
            if nm not in self.table:
                raise KeyError(f"unknown logical axis {nm!r}")
            ax = self.table[nm]
            if ax is None:
                phys.append(None)
            elif isinstance(ax, tuple):
                ax = tuple(a for a in ax if a not in used)
                used.update(ax)
                phys.append(ax if ax else None)
            else:
                if ax in used:
                    phys.append(None)
                else:
                    used.add(ax)
                    phys.append(ax)
        return P(*phys)


_state = threading.local()


def current_rules() -> Optional[ShardingRules]:
    return getattr(_state, "rules", None)


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_rules(rules: ShardingRules, mesh: Optional[Mesh] = None):
    prev_r = getattr(_state, "rules", None)
    prev_m = getattr(_state, "mesh", None)
    _state.rules = rules
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.rules = prev_r
        _state.mesh = prev_m


def attention_overrides(cfg, tp: int, kind: str = "train") -> Dict[str, PhysAxes]:
    """Divisibility-aware attention mapping (standard production practice).

    * heads %% tp == 0: shard heads over the TP axis; KV heads replicated
      when they do not divide (GQA groups share replicated KV).
    * otherwise: *sequence parallelism* on the TP axis for train/prefill
      (any seq divides 16), and split-KV decode (kv_seq over the TP axis)
      for decode — attention weights then shard over fsdp only.
    Naive (no-override) mapping triggers XLA involuntary full remat on
    non-divisible heads: ~20x HBM + collective inflation (EXPERIMENTS §Perf
    iteration 0 documents the before/after).
    """
    ov: Dict[str, PhysAxes] = {}
    if cfg.family == "xlstm":
        return ov  # flat-dim projections; head dims never sharded
    if cfg.heads % tp == 0:
        if cfg.kv_heads % tp:
            ov["kv_heads"] = None
    else:
        ov["heads"] = None
        ov["kv_heads"] = None
        if kind == "decode":
            ov["kv_seq"] = "model"
        else:
            ov["seq"] = "model"
    d_ff = cfg.moe.d_ff if cfg.moe is not None else cfg.d_ff
    if d_ff and d_ff % tp:
        ov["mlp"] = None
    return ov


def make_rules(
    mesh_axes: Sequence[str],
    overrides: Optional[Dict[str, PhysAxes]] = None,
) -> ShardingRules:
    """Restrict DEFAULT_RULES to the axes present in the mesh (e.g. no
    'pod' on the single-pod mesh) and apply overrides."""
    axes = set(mesh_axes)
    table: Dict[str, PhysAxes] = {}
    for k, v in DEFAULT_RULES.items():
        if v is None:
            table[k] = None
        elif isinstance(v, tuple):
            kept = tuple(a for a in v if a in axes)
            table[k] = kept if kept else None
        else:
            table[k] = v if v in axes else None
    if overrides:
        table.update(overrides)
    return ShardingRules(table)


def _manual_axes_in_context() -> Optional[set]:
    """Axes marked Manual in the current abstract mesh (inside shard_map),
    or None when no abstract mesh / no manual axes."""
    am = jax.sharding.get_abstract_mesh()
    manual = {
        name
        for name, t in zip(am.axis_names, am.axis_types)
        if t == jax.sharding.AxisType.Manual
    }
    return manual or None


def _project_spec(spec: P, drop: set) -> P:
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a not in drop)
            out.append(kept if kept else None)
        else:
            out.append(None if entry in drop else entry)
    return P(*out)


def shard_hint(x: jax.Array, names: Sequence[Optional[str]]) -> jax.Array:
    """Annotate an activation with logical axes; no-op without rules/mesh.

    Inside a partial-manual shard_map region the constraint is projected
    onto the remaining auto axes and expressed against the context mesh.
    """
    rules = current_rules()
    if rules is None:
        return x
    spec = rules.spec(names)
    manual = _manual_axes_in_context()
    if manual is not None:
        spec = _project_spec(spec, manual)
        try:
            return jax.lax.with_sharding_constraint(x, spec)
        except Exception:
            return x
    mesh = getattr(_state, "mesh", None)
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def logical_spec_tree(spec_names_tree, rules: ShardingRules):
    """Map a pytree of logical-name tuples to PartitionSpecs."""
    return jax.tree_util.tree_map(
        lambda names: rules.spec(names),
        spec_names_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(n, (str, type(None))) for n in x),
    )


def named_sharding_tree(spec_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )

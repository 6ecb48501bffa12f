"""Distributed train-step factory.

Two data-parallel modes, both RailX-mapped:

* ``gspmd_fsdp`` — parameters sharded per the logical rules (fsdp->data,
  tp->model, expert->data); XLA inserts the per-layer all-gather /
  reduce-scatter inside the layer scan (ZeRO-3).  The byte structure over
  the mesh axes is already hierarchical: gradients are reduce-scattered on
  the rail ("data") axis and only 1/|data|-sized shards cross the slow
  ("pod") axis — the paper's Eq. 8 placement realized by sharding.

* ``manual_hier`` — parameters replicated over the DP axes; the step runs
  inside a *partial-manual* shard_map (manual: pod+data, auto: model) and
  applies the explicit RailX collective schedule from collectives/:
  ``flat`` (baseline psum), ``hierarchical`` (Eq. 8: RS(data) -> AR(pod)
  -> AG(data)), or ``compressed`` (int8 on the pod phase).  This is the
  paper-faithful executable form; for MoE archs use gspmd_fsdp (their EP
  shard_map cannot nest inside another manual region).

Both modes support microbatch gradient accumulation (scan) and remat.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..collectives.schedules import (
    all_gather_axis,
    all_reduce_axis,
    reduce_scatter_axis,
    tree_hierarchical_all_reduce,
)
from ..collectives.compression import compressed_hierarchical_all_reduce
from ..models.model_zoo import ModelZoo
from ..parallel.sharding import ShardingRules, logical_spec_tree, make_rules, use_rules
from . import optimizer as opt_lib


@dataclasses.dataclass(frozen=True)
class StepArtifacts:
    step_fn: Callable
    param_sharding: Any
    opt_sharding: Any
    batch_sharding: Any
    rules: ShardingRules


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_spec(mesh: Mesh) -> P:
    return P(_dp_axes(mesh), None)


def batch_specs_tree(mesh: Mesh, example: Dict[str, Any]) -> Dict[str, P]:
    """Per-key batch PartitionSpecs: batch dim over the DP axes; positions3
    is (3, B, S).  Batch dims that do not divide the DP extent (e.g. the
    long_500k single-request decode) stay unsharded."""
    dp = _dp_axes(mesh)
    dp_size = _axis_prod(mesh, dp)
    out: Dict[str, P] = {}
    for key, leaf in example.items():
        ndim = len(leaf.shape)
        bdim = 1 if key == "positions3" else 0
        shard = dp if leaf.shape[bdim] % max(dp_size, 1) == 0 else None
        if key == "positions3":
            out[key] = P(None, shard, *([None] * (ndim - 2)))
        else:
            out[key] = P(shard, *([None] * (ndim - 1)))
    return out


def sanitize_specs(spec_tree, shapes_tree, mesh: Mesh):
    """Drop sharding on dims the mesh axes cannot divide (jit input
    shardings must divide exactly; e.g. whisper's 51866 vocab over 16)."""

    def fix(spec: P, leaf) -> P:
        dims = list(leaf.shape)
        out = []
        for i, entry in enumerate(spec):
            if entry is None or i >= len(dims):
                out.append(None if i >= len(dims) else entry)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = _axis_prod(mesh, axes)
            out.append(entry if size and dims[i] % size == 0 else None)
        return P(*out)

    return jax.tree_util.tree_map(
        fix, spec_tree, shapes_tree, is_leaf=lambda x: isinstance(x, P)
    )


def make_train_step(
    zoo: ModelZoo,
    opt_cfg: opt_lib.AdamWConfig,
    mesh: Mesh,
    batch_example: Dict[str, Any],
    dp_mode: str = "gspmd_fsdp",
    schedule: str = "hierarchical",
    microbatches: int = 1,
    rules_overrides: Optional[Dict[str, Any]] = None,
) -> StepArtifacts:
    overrides = dict(rules_overrides or {})
    if dp_mode == "manual_hier":
        # params replicated over DP axes; batch sharding handled manually.
        overrides.setdefault("fsdp", None)
        overrides.setdefault("expert", None)
    rules = make_rules(tuple(mesh.shape.keys()), overrides)
    pspecs = logical_spec_tree(zoo.param_specs(), rules)
    params_shapes = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0)))
    pspecs = sanitize_specs(pspecs, params_shapes, mesh)
    param_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
    opt_specs = opt_lib.state_specs(pspecs)
    opt_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    bspec = batch_specs_tree(mesh, batch_example)
    batch_sharding = {k: NamedSharding(mesh, s) for k, s in bspec.items()}
    dp_axes = _dp_axes(mesh)

    def split_micro(batch):
        if microbatches == 1:
            return batch

        def split(key, x):
            bdim = 1 if key == "positions3" else 0
            shape = list(x.shape)
            shape[bdim : bdim + 1] = [microbatches, shape[bdim] // microbatches]
            x = x.reshape(shape)
            return jnp.moveaxis(x, bdim, 0)

        return {k: split(k, v) for k, v in batch.items()}

    def accum_grads(loss_fn, params, batch):
        """Microbatched value-and-grad with jnp accumulation."""
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            return loss, metrics, grads
        mb = split_micro(batch)

        def body(carry, mbatch):
            acc, loss_acc = carry
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mbatch
            )
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            return (acc, loss_acc + loss), metrics

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (grads, loss_sum), metrics = jax.lax.scan(body, (zeros, 0.0), mb)
        grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        return loss_sum / microbatches, metrics, grads

    def loss_fn(params, batch):
        return zoo.loss(params, batch)

    def gspmd_artifacts() -> StepArtifacts:
        def step(params, opt_state, batch):
            with use_rules(rules, mesh):
                loss, metrics, grads = accum_grads(loss_fn, params, batch)
                new_params, new_opt, opt_metrics = opt_lib.apply(
                    opt_cfg, opt_state, params, grads
                )
            metrics = dict(metrics)
            metrics.update(opt_metrics)
            metrics["loss"] = loss
            return new_params, new_opt, metrics

        jitted = jax.jit(
            step,
            in_shardings=(param_sharding, opt_sharding, batch_sharding),
            out_shardings=(param_sharding, opt_sharding, None),
            donate_argnums=(0, 1),
        )
        return StepArtifacts(jitted, param_sharding, opt_sharding, batch_sharding, rules)

    if dp_mode == "gspmd_fsdp":
        return gspmd_artifacts()

    if dp_mode != "manual_hier":
        raise ValueError(dp_mode)

    # ---- manual_hier: explicit RailX schedule on the DP axes -------------
    intra, inter = ("data",), ("pod",)
    intra = tuple(a for a in intra if a in mesh.shape)
    inter = tuple(a for a in inter if a in mesh.shape)

    def reduce_grads(grads):
        if schedule == "flat" or not intra:
            return jax.tree_util.tree_map(
                lambda g: all_reduce_axis(g, dp_axes) / _dp_size(mesh), grads
            )
        if schedule == "hierarchical":
            red = functools.partial(
                tree_hierarchical_all_reduce,
                intra_axes=intra, inter_axes=inter if inter else (),
            )
            grads = red(grads)
            return jax.tree_util.tree_map(lambda g: g / _dp_size(mesh), grads)
        if schedule == "compressed":
            def one(g):
                shape = g.shape
                flat = g.reshape(-1)
                pad = (-flat.shape[0]) % _axis_prod(mesh, intra)
                if pad:
                    flat = jnp.pad(flat, (0, pad))
                out = compressed_hierarchical_all_reduce(flat, intra, inter or intra)
                if pad:
                    out = out[:-pad]
                return out.reshape(shape) / _dp_size(mesh)
            return jax.tree_util.tree_map(one, grads)
        raise ValueError(schedule)

    def body(params, opt_state, batch):
        loss, metrics, grads = accum_grads(loss_fn, params, batch)
        with jax.named_scope("grad_reduce"):
            grads = reduce_grads(grads)
        loss = jax.lax.pmean(loss, dp_axes)
        metrics = jax.tree_util.tree_map(lambda m: jax.lax.pmean(m, dp_axes), metrics)
        new_params, new_opt, opt_metrics = opt_lib.apply(
            opt_cfg, opt_state, params, grads
        )
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    manual_axes = set(dp_axes)
    # shard_map in_specs may only reference the manual axes; the model-axis
    # (TP) sharding rides on the values themselves (GSPMD "auto").
    no_dp = lambda tree: jax.tree_util.tree_map(
        lambda s: P(*(_keep_axes(s, manual_axes))), tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(no_dp(pspecs), no_dp(opt_specs), bspec),
        out_specs=(no_dp(pspecs), no_dp(opt_specs), P()),
        axis_names=manual_axes,
        check_vma=False,
    )

    def step(params, opt_state, batch):
        with use_rules(rules, mesh):
            return mapped(params, opt_state, batch)

    jitted = jax.jit(
        step,
        in_shardings=(param_sharding, opt_sharding, batch_sharding),
        out_shardings=(param_sharding, opt_sharding, None),
        donate_argnums=(0, 1),
    )
    return StepArtifacts(jitted, param_sharding, opt_sharding, batch_sharding, rules)


def _keep_axes(spec: P, axes: set) -> Tuple:
    """Project a PartitionSpec onto a subset of mesh axes."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in axes)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in axes else None)
    return tuple(out)


def _dp_size(mesh: Mesh) -> int:
    n = 1
    for a in _dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def _axis_prod(mesh: Mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n

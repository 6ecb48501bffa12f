"""Pipeline parallelism over a mesh axis (GPipe + 1F1B schedules).

RailX maps PP onto a rail-ring dimension (Table 4: P2P ring traffic, the
lightest of the parallelisms — the mapping solver gives it the fewest
rails).  Here PP is implemented with ``shard_map`` over a ``pipe`` axis:
stage s holds layer block s (params sharded over the axis on the stacked
layer dim), activations move with ``jax.lax.ppermute`` — the canonical
jax-native pipeline (no torch.distributed semantics).

``pipeline_forward`` runs num_stages + num_micro - 1 ticks of a rotating
microbatch buffer (the standard collective-matmul-style formulation that
keeps every stage busy; arXiv:2211.05102).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax

import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    micro_inputs: jax.Array,
    axis: str = "pipe",
):
    """Run inside shard_map with ``axis`` manual.

    stage_params: this stage's layer-block params (already sharded).
    micro_inputs: (M_local, ...) microbatches resident on stage 0
                  (other stages pass zeros of the same shape).
    Returns (M_local, ...) outputs resident on the last stage.

    Schedule: GPipe-style fill-drain over T = M + S - 1 ticks; activations
    ppermute one hop per tick.
    """
    S = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    M = micro_inputs.shape[0]
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    buf = jnp.zeros_like(micro_inputs[0])
    outputs = jnp.zeros_like(micro_inputs)

    def tick(carry, t):
        buf, outputs = carry
        # stage 0 injects microbatch t (if in range) else keeps incoming
        inject = jnp.where(t < M, t, M - 1)
        fresh = micro_inputs[inject]
        x = jnp.where((idx == 0) & (t < M), fresh, buf)
        y = stage_fn(stage_params, x)
        # last stage records output for microbatch t - (S - 1)
        out_slot = t - (S - 1)
        do_write = (idx == S - 1) & (out_slot >= 0)
        outputs = jax.lax.cond(
            do_write,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(out_slot, 0), 0
            ),
            lambda o: o,
            outputs,
        )
        buf = jax.lax.ppermute(y, axis, perm)
        return (buf, outputs), None

    (buf, outputs), _ = jax.lax.scan(tick, (buf, outputs), jnp.arange(T))
    return outputs


def make_pipelined_apply(
    mesh: Mesh,
    stage_fn: Callable,
    num_micro: int,
    axis: str = "pipe",
):
    """Wrap stage_fn into a jitted pipelined apply.

    params: pytree with leading dim == num_stages (sharded over ``axis``).
    inputs: (num_micro, micro_batch, ...) replicated; returns outputs from
    the last stage, broadcast to all stages for convenience.
    """

    def body(params, inputs):
        local_params = jax.tree_util.tree_map(lambda a: a[0], params)
        outs = pipeline_forward(stage_fn, local_params, inputs, axis=axis)
        # broadcast final outputs from the last stage to all stages
        # (mask + psum: ppermute cannot express one-to-many)
        last = jax.lax.axis_size(axis) - 1
        outs = jnp.where(jax.lax.axis_index(axis) == last, outs, 0)
        return jax.lax.psum(outs, axis)

    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(mapped)

"""Mixture-of-Experts FFN with expert parallelism over the RailX rail-ring
all-to-all dimension (paper §3.3.4 / Figure 9 / Table 4 "Expert (E)" row).

Two functionally equivalent implementations:

* ``moe_ffn_dense`` — scatter/gather capacity dispatch on one device (or
  pure GSPMD).  O(T*K + E*C*D); used for smoke tests and as the oracle.
* ``moe_ffn_ep`` — shard_map expert parallelism: local top-k routing,
  ``lax.all_to_all`` over the ``ep`` mesh axis (dispatch), expert FFN with
  manual tensor parallelism over the ``tp`` axis, reverse all-to-all
  (combine).  This is precisely the traffic the paper maps onto rail-ring
  all-to-all, and the collective bytes show up in the dry-run HLO.

Router: softmax top-k with aux load-balancing loss (paper §A.4 Listing 1:
``aux_loss``, coeff 0.01, alltoall dispatcher).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import current_mesh, shard_hint
from .common import DTypes, Params, init_linear, linear_specs, trunc_normal


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                  # per-expert intermediate
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    aux_loss_coeff: float = 0.01
    num_shared_experts: int = 0
    router_dtype: Any = jnp.float32
    ep_axis: str = "data"      # mesh axis carrying expert parallelism
    tp_axis: str = "model"     # mesh axis carrying tensor parallelism
    token_scatter: bool = False  # M4: shard expert queues over TP (see body)


def init_moe(key, cfg: MoEConfig, dt: DTypes) -> Params:
    ks = jax.random.split(key, 5)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    s_in = 1.0 / math.sqrt(D)
    s_out = 1.0 / math.sqrt(F)
    p: Params = {
        "router": init_linear(ks[0], D, E, dt),
        "wi": trunc_normal(ks[1], (E, D, F), s_in, dt.param),
        "wg": trunc_normal(ks[2], (E, D, F), s_in, dt.param),
        "wo": trunc_normal(ks[3], (E, F, D), s_out, dt.param),
    }
    if cfg.num_shared_experts:
        from .common import init_swiglu

        p["shared"] = init_swiglu(ks[4], D, F * cfg.num_shared_experts, dt)
    return p


def moe_specs(cfg: MoEConfig) -> Params:
    p: Params = {
        "router": linear_specs((None, None)),
        "wi": ("expert", None, "mlp"),
        "wg": ("expert", None, "mlp"),
        "wo": ("expert", "mlp", None),
    }
    if cfg.num_shared_experts:
        from .common import swiglu_specs

        p["shared"] = swiglu_specs()
    return p


# ---------------------------------------------------------------------------
# Routing (shared by both paths; operates on local tokens)
# ---------------------------------------------------------------------------


def _route(
    p: Params, cfg: MoEConfig, xt: jax.Array, dt: DTypes, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns (src_token (E,C), slot_gate (E,C), slot_valid (E,C), aux,
    router probs)."""
    T, D = xt.shape
    E, K = cfg.num_experts, cfg.top_k
    logits = (xt @ dt.c(p["router"]["w"])).astype(cfg.router_dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)                   # (T, K)
    gate_vals = gate_vals / jnp.clip(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), cfg.router_dtype).at[gate_idx.reshape(-1)].add(1.0) / (T * K)
    aux = cfg.aux_loss_coeff * E * jnp.sum(me * ce)

    # position-in-expert via stable sort (O(TK log TK), ~MB-scale buffers)
    # instead of the classic one-hot cumsum (O(TK * E) — 268 MB of int32
    # per 94 layers for qwen3-moe; see EXPERIMENTS §Perf iteration M2).
    flat_e = gate_idx.reshape(-1)                                   # (T*K,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    pos_sorted = jnp.arange(T * K) - starts[sorted_e]
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)      # (T*K,)
    keep = pos < capacity
    slot = jnp.where(keep, flat_e * capacity + pos, E * capacity)   # dumpster

    token_ids = jnp.repeat(jnp.arange(T), K)
    src_token = (
        jnp.zeros((E * capacity + 1,), jnp.int32).at[slot].set(token_ids)[:-1]
    ).reshape(E, capacity)
    slot_gate = (
        jnp.zeros((E * capacity + 1,), gate_vals.dtype)
        .at[slot]
        .set(gate_vals.reshape(-1))[:-1]
    ).reshape(E, capacity)
    slot_valid = (
        jnp.zeros((E * capacity + 1,), bool).at[slot].set(keep)[:-1]
    ).reshape(E, capacity)
    return src_token, slot_gate, slot_valid, aux, probs


def _expert_ffn(p: Params, expert_in: jax.Array, dt: DTypes,
                wi=None, wg=None, wo=None) -> jax.Array:
    wi = dt.c(p["wi"]) if wi is None else wi
    wg = dt.c(p["wg"]) if wg is None else wg
    wo = dt.c(p["wo"]) if wo is None else wo
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, wg))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, wi)
    return jnp.einsum("ecf,efd->ecd", h, wo)


# ---------------------------------------------------------------------------
# Dense / oracle path
# ---------------------------------------------------------------------------


def moe_ffn_dense(
    p: Params, cfg: MoEConfig, x: jax.Array, dt: DTypes
) -> Tuple[jax.Array, jax.Array]:
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    capacity = int(max(1, round(cfg.capacity_factor * T * cfg.top_k / cfg.num_experts)))
    src_token, slot_gate, slot_valid, aux, _ = _route(p, cfg, xt, dt, capacity)
    expert_in = xt[src_token] * slot_valid[..., None].astype(xt.dtype)  # (E,C,D)
    expert_out = _expert_ffn(p, expert_in, dt)
    weighted = expert_out * (slot_gate * slot_valid)[..., None].astype(xt.dtype)
    out = (
        jnp.zeros_like(xt)
        .at[src_token.reshape(-1)]
        .add(weighted.reshape(-1, D))
    )
    if cfg.num_shared_experts:
        from .common import swiglu

        out = out + swiglu(p["shared"], xt, dt)
    return out.reshape(B, S, D), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map all-to-all over the EP axis)
# ---------------------------------------------------------------------------


def moe_ffn_ep(
    p: Params, cfg: MoEConfig, x: jax.Array, dt: DTypes, mesh
) -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism: tokens stay batch-sharded; dispatch/combine via
    all_to_all over ``cfg.ep_axis``; expert weights sharded over the EP
    axis on the E dim and over ``cfg.tp_axis`` on the F dim."""
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    ep = mesh.shape[cfg.ep_axis]
    has_tp = (
        cfg.tp_axis in mesh.shape
        and mesh.shape[cfg.tp_axis] > 1
        and cfg.tp_axis != cfg.ep_axis
    )
    assert E % ep == 0, (E, ep)
    batch_axes = tuple(a for a in ("pod", cfg.ep_axis) if a in mesh.shape)
    tp_spec = cfg.tp_axis if has_tp else None

    tp = mesh.shape.get(cfg.tp_axis, 1) if has_tp else 1

    def body(xb, router_w, wi, wg, wo):
        # xb: (B_local, S, D); w*: (E/ep, D, F/tp) local shards
        Bl = xb.shape[0]
        Tl = Bl * S
        xt = xb.reshape(Tl, D)
        capacity = int(max(1, round(cfg.capacity_factor * Tl * K / E)))
        if has_tp:
            capacity = ((capacity + tp - 1) // tp) * tp
        src_token, slot_gate, slot_valid, aux, _ = _route(
            {"router": {"w": router_w}}, cfg, xt, dt, capacity
        )
        expert_in = xt[src_token] * slot_valid[..., None].astype(xt.dtype)
        if has_tp and cfg.token_scatter:
            # token-dim sharding over TP (M4, EXPERIMENTS §Perf): each TP
            # rank dispatches its 1/tp slice of every expert queue, so the
            # rail-ring all-to-all moves 1/tp the bytes; the full queue is
            # re-gathered on the fast intra-node axis afterwards.
            r = jax.lax.axis_index(cfg.tp_axis)
            expert_in = jax.lax.dynamic_slice_in_dim(
                expert_in, r * (capacity // tp), capacity // tp, axis=1
            )
        expert_in = jax.lax.all_to_all(
            expert_in, cfg.ep_axis, split_axis=0, concat_axis=1, tiled=True
        )
        if has_tp and cfg.token_scatter:
            expert_in = jax.lax.all_gather(
                expert_in, cfg.tp_axis, axis=1, tiled=True
            )
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, wg))
        h = h * jnp.einsum("ecd,edf->ecf", expert_in, wi)
        out_p = jnp.einsum("ecf,efd->ecd", h, wo).astype(xt.dtype)
        if has_tp:
            if cfg.token_scatter:
                # reduce-scatter the TP contraction over the token dim:
                # 1/tp the bytes of a full psum, and the combine all-to-all
                # below also moves 1/tp the bytes.
                out_p = jax.lax.psum_scatter(
                    out_p, cfg.tp_axis, scatter_dimension=1, tiled=True
                )
            else:
                out_p = jax.lax.psum(out_p, cfg.tp_axis)
        expert_out = jax.lax.all_to_all(
            out_p, cfg.ep_axis, split_axis=1, concat_axis=0, tiled=True
        )
        if has_tp and cfg.token_scatter:
            expert_out = jax.lax.all_gather(
                expert_out, cfg.tp_axis, axis=1, tiled=True
            )
        weighted = expert_out * (slot_gate * slot_valid)[..., None].astype(xt.dtype)
        out = (
            jnp.zeros_like(xt)
            .at[src_token.reshape(-1)]
            .add(weighted.reshape(-1, D))
        )
        aux = jax.lax.pmean(aux, batch_axes)
        return out.reshape(Bl, S, D), aux

    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None, None),
            P(None, None),                  # router replicated
            P(cfg.ep_axis, None, tp_spec),  # wi
            P(cfg.ep_axis, None, tp_spec),  # wg
            P(cfg.ep_axis, tp_spec, None),  # wo
        ),
        out_specs=(P(batch_axes, None, None), P()),
        check_vma=False,
    )(x, p["router"]["w"], dt.c(p["wi"]), dt.c(p["wg"]), dt.c(p["wo"]))
    if cfg.num_shared_experts:
        from .common import swiglu

        out = out + swiglu(p["shared"], x.reshape(-1, D), dt).reshape(B, S, D)
    return out, aux.astype(jnp.float32)


def moe_ffn(
    p: Params, cfg: MoEConfig, x: jax.Array, dt: DTypes, impl: str = "auto"
) -> Tuple[jax.Array, jax.Array]:
    mesh = current_mesh()
    if impl == "ep" or (impl == "auto" and mesh is not None and cfg.ep_axis in getattr(mesh, "shape", {})):
        return moe_ffn_ep(p, cfg, x, dt, mesh)
    return moe_ffn_dense(p, cfg, x, dt)

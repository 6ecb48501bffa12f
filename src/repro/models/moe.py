"""Mixture-of-Experts FFN with expert parallelism over the RailX rail-ring
all-to-all dimension (paper §3.3.4 / Figure 9 / Table 4 "Expert (E)" row).

Three implementations:

* ``moe_ffn_held`` — one chip's share of an expert-parallel layer, with no
  token dropped: the router scores every expert, the (token, expert)
  pairs routed to the experts this chip holds are sorted by expert and
  go through grouped matrix products over the held experts (Pallas
  megablox ``gmm`` on the TPU, ``lax.ragged_dot`` elsewhere).  The pair
  buffer keeps its worst-case size (T x top_k rows), but on the TPU no
  work touches a row past the held pairs: the row gather and the gated
  combine are Pallas kernels that stop at their count
  (``kernels/moe_dispatch``), and the products write only the held
  groups.  Taken whenever the configuration names the held experts
  (``MoEConfig.held_experts``); on one chip it runs without the exchange
  that would bring the other chips' tokens.

* ``moe_ffn_dense`` — scatter/gather capacity dispatch on one device (or
  pure GSPMD).  O(T*K + E*C*D); used for smoke tests and as the oracle.
* ``moe_ffn_ep`` — shard_map expert parallelism: local top-k routing,
  ``lax.all_to_all`` over the ``ep`` mesh axis (dispatch), expert FFN with
  manual tensor parallelism over the ``tp`` axis, reverse all-to-all
  (combine).  This is precisely the traffic the paper maps onto rail-ring
  all-to-all, and the collective bytes show up in the dry-run HLO.

Every path takes the router of the configuration (``route_topk``):
softmax or sigmoid (DeepSeek-V3) scores in f32, top-k, gates normalised
over the top-k and scaled by ``routed_scale``; the score correction bias
of DeepSeek-V3's aux-loss-free balancing is held at zero (top-k of the
scores themselves).  The capacity paths add an aux load-balancing loss
(paper §A.4 Listing 1: ``aux_loss``, coeff 0.01, alltoall dispatcher);
the held path has none.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.moe_dispatch import ops as dispatch_ops
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
    ep_axis: str = "data"      # mesh axis carrying expert parallelism
    tp_axis: str = "model"     # mesh axis carrying tensor parallelism
    token_scatter: bool = False  # M4: shard expert queues over TP (see body)
    scoring: str = "softmax"   # softmax | sigmoid (route_topk)
    routed_scale: float = 1.0
    held_experts: Optional[int] = None   # held path: experts on this chip
    first_held: int = 0

    @property
    def local_experts(self) -> int:
        return self.num_experts if self.held_experts is None else self.held_experts


def init_moe(key, cfg: MoEConfig, dt: DTypes) -> Params:
    ks = jax.random.split(key, 5)
    E, D, F = cfg.local_experts, cfg.d_model, cfg.d_ff
    s_in = 1.0 / math.sqrt(D)
    s_out = 1.0 / math.sqrt(F)
    p: Params = {
        "router": init_linear(ks[0], D, cfg.num_experts, dt),
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
# Capacity routing (the dense and EP paths; operates on local tokens)
# ---------------------------------------------------------------------------


def _route(
    p: Params, cfg: MoEConfig, xt: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns (src_token (E,C), slot_gate (E,C), slot_valid (E,C), aux,
    router scores)."""
    T, D = xt.shape
    E, K = cfg.num_experts, cfg.top_k
    gate_vals, gate_idx, probs = route_topk(p["router"]["w"], cfg, xt)   # (T, K)

    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[gate_idx.reshape(-1)].add(1.0) / (T * K)
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
    src_token, slot_gate, slot_valid, aux, _ = _route(p, cfg, xt, capacity)
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
            {"router": {"w": router_w}}, cfg, xt, capacity
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


# ---------------------------------------------------------------------------
# Held path: one chip's experts, dropless (grouped matrix products)
# ---------------------------------------------------------------------------


def route_topk(router_w: jax.Array, cfg: MoEConfig, xt: jax.Array):
    """(gates (T, K) f32, expert ids (T, K), scores (T, E) f32) over all
    ``num_experts``: scores in f32 at full precision, top-k, gates
    normalised over the top-k and scaled by ``routed_scale``."""
    logits = jnp.dot(xt.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif cfg.scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {cfg.scoring!r}")
    gates, idx = jax.lax.top_k(scores, cfg.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * cfg.routed_scale, idx, scores


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """megablox tiles (tm, tk, tn), for ``gmm`` (tm x tk by tk x tn into a
    tm x tn accumulator) and ``tgmm`` (tk x tm by tm x tn into tk x tn):
    512 rows, a width up to 1,536 whole and a larger one in 1,024s; then
    the largest tile that halves into a divisor of its dimension is halved
    while the double-buffered bf16 blocks and the f32 accumulator of
    either kernel exceed 12 MiB of VMEM (the limit is 16)."""
    def vmem(tm, tk, tn):
        acc = max(tm, tk) * tn
        return 2 * 2 * (tm * tk + tk * tn + acc) + 4 * acc

    tiles = {"m": min(512, m), "k": k if k <= 1536 else 1024, "n": n if n <= 1536 else 1024}
    dims = {"m": m, "k": k, "n": n}
    while vmem(tiles["m"], tiles["k"], tiles["n"]) > 12 * 2**20:
        halvable = [d for d in ("n", "k", "m")
                    if tiles[d] % 256 == 0 and dims[d] % (tiles[d] // 2) == 0]
        if not halvable:
            break
        d = max(halvable, key=lambda d: tiles[d])
        tiles[d] //= 2
    return tiles["m"], tiles["k"], tiles["n"]


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _grouped(x: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    """Row group g of x (sizes[g] consecutive rows, one group for each
    w[g]) times w[g].  Rows past the groups are not written on the TPU
    (megablox leaves them as the buffer held, and its backward reads
    none of them) and come out zero elsewhere."""
    if not _on_cpu():
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(x, w, sizes, x.dtype, _gmm_tiling, interpret=dispatch_ops._on_cpu())
    return jax.lax.ragged_dot(x, w, sizes).astype(x.dtype)


def moe_ffn_held(
    p: Params, cfg: MoEConfig, x: jax.Array, dt: DTypes
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """One chip's share of the layer: the routed experts' part of the
    result from the experts [first_held, first_held + held_experts), for
    every token routed to them, plus the shared expert.  Returns (out, aux
    (zero), counters): ``moe_tokens_held`` the (token, expert) pairs routed
    to held experts, ``moe_max_load`` the busiest held expert's pairs over
    the held experts' mean."""
    B, S, D = x.shape
    T, K, Eh = B * S, cfg.top_k, cfg.held_experts
    xt = x.reshape(T, D)
    with jax.named_scope("moe_route"):
        gates, idx, _ = route_topk(p["router"]["w"], cfg, xt)
        local = idx.reshape(-1) - cfg.first_held
        group = jnp.where((local >= 0) & (local < Eh), local, Eh)   # Eh: not held
        order = jnp.argsort(group, stable=True)
        inv = jnp.argsort(order)
        sizes = jnp.bincount(group, length=Eh + 1).astype(jnp.int32)[:Eh]
        held = jnp.sum(sizes)
    with jax.named_scope("moe_experts"):
        # pairs of experts not held sort last: rows past ``held`` are not
        # read, and on the TPU not written either
        impl = "ref" if _on_cpu() else "kernel"
        pos = inv.reshape(T, K)
        xs = dispatch_ops.dispatch(xt, order, pos, sizes, impl)
        h = jax.nn.silu(_grouped(xs, dt.c(p["wg"]), sizes)) * _grouped(xs, dt.c(p["wi"]), sizes)
        y = _grouped(h, dt.c(p["wo"]), sizes)
        out = dispatch_ops.combine(y, gates, order, pos, sizes, impl).reshape(B, S, D)
    if cfg.num_shared_experts:
        from .common import swiglu

        with jax.named_scope("moe_shared"):
            out = out + swiglu(p["shared"], x, dt)
    load = sizes.astype(jnp.float32)
    mean = jnp.mean(load)
    stats = {"moe_tokens_held": held,
             "moe_max_load": jnp.where(mean > 0, jnp.max(load) / jnp.maximum(mean, 1e-9), 0.0)}
    return out, jnp.zeros((), jnp.float32), stats


def moe_ffn(
    p: Params, cfg: MoEConfig, x: jax.Array, dt: DTypes, impl: str = "auto"
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """(out, aux loss, counters); the counters are the held path's, empty
    for the capacity paths."""
    if cfg.held_experts is not None:
        return moe_ffn_held(p, cfg, x, dt)
    mesh = current_mesh()
    if impl == "ep" or (impl == "auto" and mesh is not None and cfg.ep_axis in getattr(mesh, "shape", {})):
        return (*moe_ffn_ep(p, cfg, x, dt, mesh), {})
    return (*moe_ffn_dense(p, cfg, x, dt), {})

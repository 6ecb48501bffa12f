"""AdamW with sharded state (no optax dependency).

Optimizer state mirrors the parameter sharding (first/second moments take
the same PartitionSpec as their parameter), supports bf16 params with f32
moments, decoupled weight decay, global-norm clipping, and the standard
warmup+cosine schedule.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1
    moment_dtype: Any = jnp.float32


def lr_schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    s = step.astype(jnp.float32)
    warm = s / jnp.maximum(1.0, cfg.warmup_steps)
    prog = (s - cfg.warmup_steps) / jnp.maximum(
        1.0, cfg.total_steps - cfg.warmup_steps
    )
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(math.pi * prog))
    return cfg.lr * jnp.where(s < cfg.warmup_steps, warm, cos)


def init(cfg: AdamWConfig, params) -> AdamWState:
    zeros = lambda p: jnp.zeros(p.shape, cfg.moment_dtype)
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree_util.tree_map(zeros, params),
        nu=jax.tree_util.tree_map(zeros, params),
    )


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    )


def apply(
    cfg: AdamWConfig, state: AdamWState, params, grads
) -> Tuple[Any, AdamWState, Dict[str, jax.Array]]:
    with jax.named_scope("optimizer"):
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12))
        step = state.step + 1
        lr = lr_schedule(cfg, step)
        b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
        b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

        def upd(p, g, mu, nu):
            g = g.astype(jnp.float32) * scale
            mu = cfg.b1 * mu + (1 - cfg.b1) * g.astype(cfg.moment_dtype)
            nu = cfg.b2 * nu + (1 - cfg.b2) * jnp.square(g).astype(cfg.moment_dtype)
            mhat = mu.astype(jnp.float32) / b1c
            vhat = nu.astype(jnp.float32) / b2c
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
            if p.ndim >= 2:  # decoupled decay on matrices only
                delta = delta + cfg.weight_decay * p.astype(jnp.float32)
            new_p = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
            return new_p, mu, nu

        flat_p, tdef = jax.tree_util.tree_flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_mu = tdef.flatten_up_to(state.mu)
        flat_nu = tdef.flatten_up_to(state.nu)
        out = [upd(p, g, m, n) for p, g, m, n in zip(flat_p, flat_g, flat_mu, flat_nu)]
        new_params = tdef.unflatten([o[0] for o in out])
        new_mu = tdef.unflatten([o[1] for o in out])
        new_nu = tdef.unflatten([o[2] for o in out])
        metrics = {"grad_norm": gnorm, "lr": lr}
        return new_params, AdamWState(step, new_mu, new_nu), metrics


def state_specs(param_specs_tree) -> AdamWState:
    """Optimizer-state spec tree mirroring the param specs."""
    from jax.sharding import PartitionSpec as P

    return AdamWState(
        step=P(),
        mu=param_specs_tree,
        nu=param_specs_tree,
    )

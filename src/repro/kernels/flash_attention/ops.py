"""jit'd public wrapper for the flash attention kernel.

``flash_attention`` takes model-layout tensors q (B, S, H, Dh),
k (B, S, Hk, Dh), v (B, S, Hk, Dv), transposes to kernel layout and runs the Pallas
kernels through a custom_vjp: the forward saves the logsumexp rows and the
backward runs the blocked dq and dk/dv kernels.  The platform picks the
mode: interpret on the CPU, compiled on the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import (
    flash_attention_bwd,
    flash_attention_fwd,
    flash_attention_fwd_lse,
)


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def _flash(q, k, v, causal, window, scale, q_offset):
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, scale=scale,
        q_offset=q_offset, interpret=_on_cpu(),
    )


def _flash_fwd(q, k, v, causal, window, scale, q_offset):
    out, lse = flash_attention_fwd_lse(
        q, k, v, causal=causal, window=window, scale=scale,
        q_offset=q_offset, interpret=_on_cpu(),
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, scale, q_offset, res, g):
    q, k, v, o, lse = res
    return flash_attention_bwd(
        q, k, v, o, lse, g, causal=causal, window=window, scale=scale,
        q_offset=q_offset, interpret=_on_cpu(),
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
) -> jax.Array:
    """Model layout: q (B, S, H, Dh), k (B, S, Hk, Dh), v (B, S, Hk, Dv)
    -> (B, S, H, Dv)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, causal, window, scale, q_offset)
    return jnp.swapaxes(out, 1, 2)

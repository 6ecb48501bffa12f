"""Pure-jnp oracle for the flash attention kernel.

Semantics: causal (optionally sliding-window) GQA attention,
q (B, H, Sq, Dh), k (B, Hk, Skv, Dh), v (B, Hk, Skv, Dv), f32
accumulation, output (B, H, Sq, Dv) in q.dtype.
``q_offset`` places the q block at absolute position q_offset in the kv
timeline (0 for training/prefill).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
) -> jax.Array:
    B, H, Sq, Dh = q.shape
    Hk = k.shape[1]
    Skv = k.shape[2]
    group = H // Hk
    if scale is None:
        scale = Dh ** -0.5
    qf = q.astype(jnp.float32) * scale
    qg = qf.reshape(B, Hk, group, Sq, Dh)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32))
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, Sq, v.shape[-1]).astype(q.dtype)

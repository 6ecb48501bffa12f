"""Mamba2 SSD chunk-scan as a Pallas TPU kernel.

The SSD dual form splits the scan into chunk-local quadratic attention-like
matmuls plus an inter-chunk state recurrence — exactly the structure that
feeds the MXU.  Grid (B, H, nc) with the chunk axis innermost: the running
state (P, N) persists in VMEM scratch across chunk steps (TPU grids execute
sequentially), so each grid step does

    intra:  (C x C decay-masked) (C_t . B_s) matmul against x*dt
    inter:  C_t . (decay * state)
    state' = chunk_decay * state + sum_s decay_to_end(s) * B_s (x dt)_s

Block shapes: one chunk of 64-256 rows x (P or N <= 128) columns — matmul
dims MXU-aligned; VMEM ~ (3*C*N + C*P + C*C + P*N)*4 B < 1 MB at C=128,
P=N=64-128.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref,
    state_scr,
    *, chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)       # (C, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)        # (C,)
    Bm = b_ref[0].astype(jnp.float32)               # (C, N)
    Cm = c_ref[0].astype(jnp.float32)               # (C, N)
    A = a_ref[0].astype(jnp.float32)                # scalar

    dA = dt * A                                     # (C,) negative increments
    cum = jnp.cumsum(dA)                            # (C,)
    # intra-chunk decay-masked kernel
    seg = cum[:, None] - cum[None, :]               # (t, s)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    L = jnp.where(causal, jnp.exp(seg), 0.0)        # (t, s)
    CB = Cm @ Bm.T                                  # (t, s)
    xdt = x * dt[:, None]                           # (s, P)
    y = (CB * L) @ xdt                              # (t, P)
    # inter-chunk: y += (C_t exp(cum_t)) . state
    state = state_scr[...]                          # (P, N)
    y = y + (jnp.exp(cum)[:, None] * Cm) @ state.T
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)
    # state update
    decay_to_end = jnp.exp(cum[-1] - cum)           # (s,)
    contrib = (xdt * decay_to_end[:, None]).T @ Bm  # (P, N)
    state_scr[...] = state * jnp.exp(cum[-1]) + contrib


def ssd_fwd(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)
    Bm: jax.Array,     # (B, S, N)
    Cm: jax.Array,     # (B, S, N)
    A: jax.Array,      # (H,)
    *,
    chunk: int = 64,
    interpret: bool,
) -> jax.Array:
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, ic: (b, ic, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, ic: (b, ic, h)),
            pl.BlockSpec((1, chunk, N), lambda b, h, ic: (b, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, ic: (b, ic, 0)),
            pl.BlockSpec((1,), lambda b, h, ic: (h,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, P), lambda b, h, ic: (b, ic, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        name="ssd_fwd",
        interpret=interpret,
    )(x, dt, Bm, Cm, A)

"""Chunkwise mLSTM as a Pallas TPU kernel (xLSTM matrix-memory cell).

Grid (B, H, nc), chunk axis innermost; the stabilized state (C (D, D),
n (D,), m scalar) persists in VMEM scratch across chunk steps.  Per chunk:

  intra:  decay-masked (q k^T) x v matmuls (MXU)
  inter:  q @ C with per-row amplitude exp(cumf_t + m_in - m_t)
  state:  C' = exp(m_in + F - m_out) C + sum_s exp(e_s - m_out) k_s v_s^T

identical math to models/ssm._mlstm_chunked — the jnp chunked form and the
sequential ref.py both serve as oracles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _mlstm_kernel(
    q_ref, k_ref, v_ref, i_ref, f_ref, y_ref,
    c_scr, n_scr, m_scr,
    *, chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG)

    q = q_ref[0, :, 0, :].astype(jnp.float32)       # (C, D)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    ig = i_ref[0, :, 0].astype(jnp.float32)         # (C,)
    lf = f_ref[0, :, 0].astype(jnp.float32)

    cumf = jnp.cumsum(lf)                            # (C,)
    m_in = m_scr[0]
    # intra exponents
    b = cumf[:, None] - cumf[None, :] + ig[None, :]  # (t, s)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    b = jnp.where(causal, b, NEG)
    c_t = cumf + m_in                                # (t,)
    m_t = jnp.maximum(jnp.max(b, axis=1), c_t)
    w = jnp.exp(b - m_t[:, None])                    # (t, s)
    qk = q @ k.T
    y = (w * qk) @ v                                 # (t, D)
    inter_amp = jnp.exp(c_t - m_t)                   # (t,)
    y = y + inter_amp[:, None] * (q @ c_scr[...])
    n_t = w @ k + inter_amp[:, None] * n_scr[...][None, :]
    qn = jnp.sum(q * n_t, axis=1)                    # (t,)
    denom = jnp.maximum(jnp.abs(qn), jnp.exp(-m_t))
    y_ref[0, :, 0, :] = (y / denom[:, None]).astype(y_ref.dtype)
    # state update
    fe = cumf[-1]
    e_s = fe - cumf + ig                             # (s,)
    m_out = jnp.maximum(m_in + fe, jnp.max(e_s))
    amp = jnp.exp(e_s - m_out)                       # (s,)
    c_scr[...] = c_scr[...] * jnp.exp(m_in + fe - m_out) + (amp[:, None] * k).T @ v
    n_scr[...] = n_scr[...] * jnp.exp(m_in + fe - m_out) + amp @ k
    m_scr[0] = m_out


def mlstm_fwd(
    q: jax.Array,      # (B, S, H, D) pre-scaled by 1/sqrt(D)
    k: jax.Array,
    v: jax.Array,
    i_gate: jax.Array, # (B, S, H)
    logf: jax.Array,   # (B, S, H)
    *,
    chunk: int = 64,
    interpret: bool,
) -> jax.Array:
    B, S, H, D = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    kernel = functools.partial(_mlstm_kernel, chunk=chunk)
    qkv_spec = pl.BlockSpec((1, chunk, 1, D), lambda b, h, ic: (b, ic, h, 0))
    gate_spec = pl.BlockSpec((1, chunk, 1), lambda b, h, ic: (b, ic, h))
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[qkv_spec, qkv_spec, qkv_spec, gate_spec, gate_spec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((D, D), jnp.float32),
            pltpu.VMEM((D,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        name="mlstm_fwd",
        interpret=interpret,
    )(q, k, v, i_gate, logf)

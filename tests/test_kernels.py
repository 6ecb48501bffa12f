"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode on CPU; BlockSpec tiling exercised for real)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mlstm.ops import mlstm
from repro.kernels.mlstm.ref import mlstm_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref

RNG = np.random.RandomState(0)


@pytest.mark.parametrize(
    "B,H,Hk,S,Dh,causal,window,dtype",
    [
        (2, 4, 2, 256, 64, True, None, jnp.float32),
        (1, 2, 1, 128, 128, True, 64, jnp.float32),
        (2, 2, 2, 256, 32, False, None, jnp.float32),
        (1, 8, 4, 512, 64, True, 128, jnp.float32),
        (2, 4, 4, 256, 64, True, None, jnp.bfloat16),
    ],
)
def test_flash_attention_sweep(B, H, Hk, S, Dh, causal, window, dtype):
    q = jnp.array(RNG.randn(B, H, S, Dh), dtype)
    k = jnp.array(RNG.randn(B, Hk, S, Dh), dtype)
    v = jnp.array(RNG.randn(B, Hk, S, Dh), dtype)
    out = flash_attention_fwd(
        q, k, v, causal=causal, window=window, interpret=True
    )
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


@given(
    st.sampled_from([64, 128, 256]),
    st.sampled_from([32, 64]),
    st.booleans(),
)
@settings(max_examples=8, deadline=None)
def test_flash_attention_property(S, Dh, causal):
    q = jnp.array(RNG.randn(1, 2, S, Dh), jnp.float32)
    k = jnp.array(RNG.randn(1, 2, S, Dh), jnp.float32)
    v = jnp.array(RNG.randn(1, 2, S, Dh), jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=causal, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grad_via_ref():
    q = jnp.array(RNG.randn(1, 64, 2, 32), jnp.float32)
    k = jnp.array(RNG.randn(1, 64, 2, 32), jnp.float32)
    v = jnp.array(RNG.randn(1, 64, 2, 32), jnp.float32)
    g = jax.grad(lambda q, k, v: flash_attention(q, k, v).sum(), argnums=(0, 1, 2))(
        q, k, v
    )
    assert all(jnp.isfinite(x).all() for x in g)


@pytest.mark.parametrize(
    "B,S,H,P,N,ch",
    [(2, 128, 3, 32, 16, 32), (1, 64, 2, 64, 64, 64), (2, 256, 1, 16, 8, 64)],
)
def test_ssd_sweep(B, S, H, P, N, ch):
    x = jnp.array(RNG.randn(B, S, H, P), jnp.float32)
    dt = jnp.array(np.abs(RNG.randn(B, S, H)) * 0.1 + 0.01, jnp.float32)
    Bm = jnp.array(RNG.randn(B, S, N), jnp.float32)
    Cm = jnp.array(RNG.randn(B, S, N), jnp.float32)
    A = -jnp.array(np.abs(RNG.randn(H)) + 0.5, jnp.float32)
    out = ssd(x, dt, Bm, Cm, A, chunk=ch)
    ref = ssd_ref(x, dt, Bm, Cm, A)
    scale = max(1e-6, float(jnp.abs(ref).max()))
    assert float(jnp.abs(out - ref).max()) / scale < 1e-4


@pytest.mark.parametrize(
    "B,S,H,D,ch", [(2, 128, 2, 32, 32), (1, 64, 3, 16, 64), (2, 256, 1, 64, 64)]
)
def test_mlstm_sweep(B, S, H, D, ch):
    q = jnp.array(RNG.randn(B, S, H, D) / np.sqrt(D), jnp.float32)
    k = jnp.array(RNG.randn(B, S, H, D), jnp.float32)
    v = jnp.array(RNG.randn(B, S, H, D), jnp.float32)
    ig = jnp.array(RNG.randn(B, S, H), jnp.float32)
    lf = jnp.array(
        jax.nn.log_sigmoid(jnp.array(RNG.randn(B, S, H) + 2)), jnp.float32
    )
    out = mlstm(q, k, v, ig, lf, chunk=ch)
    ref = mlstm_ref(q, k, v, ig, lf)
    scale = max(1e-6, float(jnp.abs(ref).max()))
    assert float(jnp.abs(out - ref).max()) / scale < 1e-3


def test_model_ssm_equivalences():
    """Chunked forms == sequential recurrences (model-level oracles)."""
    from repro.models.common import DTypes
    from repro.models.ssm import (
        Mamba2Config, XLSTMConfig, init_mamba2, init_mlstm,
        mamba2, mamba2_init_state, mlstm as model_mlstm, mlstm_init_state,
    )

    dt = DTypes()
    cfg = Mamba2Config(d_model=32, d_state=16, head_dim=16, expand=2, chunk=8)
    p = init_mamba2(jax.random.PRNGKey(0), cfg, dt)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    y_par, _ = mamba2(p, cfg, x, dt)
    st_ = mamba2_init_state(cfg, 2)
    ys = []
    for t in range(24):
        yt, st_ = mamba2(p, cfg, x[:, t : t + 1], dt, state=st_)
        ys.append(yt)
    np.testing.assert_allclose(
        np.asarray(y_par), np.asarray(jnp.concatenate(ys, 1)), atol=1e-4
    )

    xc = XLSTMConfig(d_model=32, heads=4, chunk=8)
    pm = init_mlstm(jax.random.PRNGKey(2), xc, dt)
    y_chunk, _ = model_mlstm(pm, xc, x, dt)
    y_seq, _ = model_mlstm(pm, xc, x, dt, state=mlstm_init_state(xc, 2))
    np.testing.assert_allclose(
        np.asarray(y_chunk), np.asarray(y_seq), atol=2e-3
    )

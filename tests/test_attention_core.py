"""``models.common.sdpa``, the models' one attention core, against the
kernels' oracle ``kernels/flash_attention/ref.attention_ref``.

The oracle takes the kernel layout (B, H, S, D) and the core the model
layout (B, S, H, D), so every comparison transposes.  All at f32: both
compute f32 logits and softmax, so they agree to rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ref import attention_ref
from repro.models.common import sdpa

B, S, WINDOW = 2, 16, 5
TOL = dict(atol=1e-5, rtol=1e-5)
GROUPS = pytest.mark.parametrize("heads,kv_heads", [(4, 4), (8, 2)], ids=["group1", "group4"])


def _t(x):
    return jnp.swapaxes(x, 1, 2)


def _qkv(seed, heads, kv_heads, sq=S, skv=S, dqk=32, dv=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, sq, heads, dqk), jnp.float32)
    k = jax.random.normal(ks[1], (B, skv, kv_heads, dqk), jnp.float32)
    v = jax.random.normal(ks[2], (B, skv, kv_heads, dv), jnp.float32)
    return q, k, v


def _oracle(q, k, v, *, causal, window, q_offset=0):
    scale = q.shape[-1] ** -0.5
    out = attention_ref(_t(q), _t(k), _t(v), causal=causal, window=window, scale=scale,
                        q_offset=q_offset)
    return _t(out)


# mask: (sdpa's causal, window, traced is_global; the oracle's causal, window)
MASKS = {
    "causal": (True, None, None, True, None),
    "noncausal": (False, None, None, False, None),
    "window": (True, WINDOW, None, True, WINDOW),
    "window_global": (True, WINDOW, True, True, None),
    "window_local": (True, WINDOW, False, True, WINDOW),
}


@GROUPS
@pytest.mark.parametrize("mask", list(MASKS))
def test_sdpa_matches_oracle(mask, heads, kv_heads):
    causal, window, is_global, want_causal, want_window = MASKS[mask]
    q, k, v = _qkv(0, heads, kv_heads)
    got = jax.jit(lambda q, k, v, flag: sdpa(
        q, k, v, causal=causal, window=window, scale=q.shape[-1] ** -0.5, is_global=flag,
    ))(q, k, v, None if is_global is None else jnp.asarray(is_global))
    want = _oracle(q, k, v, causal=want_causal, window=want_window)
    np.testing.assert_allclose(got, want, **TOL)


@GROUPS
@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_sdpa_decode_over_partly_filled_cache(window, heads, kv_heads):
    """Queries at a traced offset into a cache filled up to offset + Sq;
    the unfilled tail holds large garbage that must not reach the output."""
    cache_len, sq, offset = 32, 3, 9
    q, k, v = _qkv(1, heads, kv_heads, sq=sq, skv=cache_len)
    fill = offset + sq
    k = k.at[:, fill:].set(1e4)
    v = v.at[:, fill:].set(-1e4)
    got = jax.jit(lambda q, k, v, i: sdpa(
        q, k, v, causal=True, window=window, scale=q.shape[-1] ** -0.5, q_offset=i,
    ))(q, k, v, jnp.int32(offset))
    want = _oracle(q, k[:, :fill], v[:, :fill], causal=True, window=window, q_offset=offset)
    np.testing.assert_allclose(got, want, **TOL)


@GROUPS
def test_sdpa_value_width_differs(heads, kv_heads):
    """Latent attention's widths: queries and keys of 192, values of 128."""
    q, k, v = _qkv(2, heads, kv_heads, dqk=192, dv=128)
    got = sdpa(q, k, v, causal=True, window=None, scale=192 ** -0.5)
    assert got.shape == (B, S, heads, 128)
    np.testing.assert_allclose(got, _oracle(q, k, v, causal=True, window=None), **TOL)


def test_sdpa_flash_matches_ref():
    """The kernel path (interpret mode on the CPU) against the jnp body."""
    q, k, v = _qkv(3, 4, 2, sq=128, skv=128, dqk=64, dv=64)
    kw = dict(causal=True, window=None, scale=64 ** -0.5)
    np.testing.assert_allclose(sdpa(q, k, v, impl="flash", **kw),
                               sdpa(q, k, v, impl="ref", **kw), atol=2e-5, rtol=2e-5)


def test_sdpa_flash_refuses_window():
    q, k, v = _qkv(4, 4, 4)
    with pytest.raises(ValueError, match="sliding-window"):
        sdpa(q, k, v, causal=True, window=WINDOW, scale=1.0, impl="flash")

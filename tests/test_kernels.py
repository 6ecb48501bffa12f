"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode on CPU; BlockSpec tiling exercised for real)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.flash_attention import (
    VMEM_BUDGET,
    _bwd_vmem_bytes,
    bwd_block_sizes,
    flash_attention_bwd,
    flash_attention_fwd,
    flash_attention_fwd_lse,
    kv_block_range,
    q_block_range,
    visited_share,
)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mlstm.ops import mlstm
from repro.kernels.mlstm.ref import mlstm_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref

RNG = np.random.RandomState(0)


def _masked_logsumexp(q, k, *, causal, window, q_offset):
    """logsumexp over keys of the masked, scaled logits: (B, H, Sq, 1)."""
    B, H, Sq, Dh = q.shape
    Hk, Skv = k.shape[1], k.shape[2]
    qf = q.astype(jnp.float32).reshape(B, Hk, H // Hk, Sq, Dh) * Dh ** -0.5
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, k.astype(jnp.float32))
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, -1e30)
    return jax.nn.logsumexp(logits, axis=-1, keepdims=True).reshape(B, H, Sq, 1)


@pytest.mark.parametrize("entry", ["fwd", "fwd_lse"])
@pytest.mark.parametrize(
    "B,H,Hk,Sq,Skv,q_offset,Dh,causal,window,blocks,dtype",
    [
        (2, 4, 2, 256, 256, 0, 64, True, None, None, jnp.float32),
        (1, 2, 1, 128, 128, 0, 128, True, 64, None, jnp.float32),
        (2, 2, 2, 256, 256, 0, 32, False, None, None, jnp.float32),
        (1, 8, 4, 512, 512, 0, 64, True, 128, None, jnp.float32),
        (2, 4, 4, 256, 256, 0, 64, True, None, None, jnp.bfloat16),
        # Sq != Skv at a static offset, GQA group 4, block_q != block_k
        (1, 4, 1, 128, 512, 384, 64, True, None, (64, 128), jnp.float32),
        # keys past the last query are pruned; offsets off the block grid
        # put tiles on each edge of the mask and of the visited range
        (1, 4, 4, 128, 384, 97, 64, True, 96, (32, 64), jnp.float32),
        (1, 2, 2, 128, 256, 94, 64, True, None, (32, 64), jnp.float32),
        # window smaller than one block, and larger than a block
        (1, 2, 2, 256, 256, 0, 64, True, 32, (64, 64), jnp.float32),
        (1, 2, 2, 256, 256, 0, 64, True, 96, (64, 32), jnp.float32),
        # no causal mask: every tile runs; with a window, a lower bound only
        (2, 2, 2, 256, 256, 0, 64, False, None, (128, 64), jnp.float32),
        (1, 2, 2, 256, 256, 0, 64, False, 63, (64, 64), jnp.float32),
        (1, 8, 2, 256, 256, 0, 64, True, None, (64, 128), jnp.bfloat16),
    ],
)
def test_flash_attention_sweep(
    entry, B, H, Hk, Sq, Skv, q_offset, Dh, causal, window, blocks, dtype
):
    q = jnp.array(RNG.randn(B, H, Sq, Dh), dtype)
    k = jnp.array(RNG.randn(B, Hk, Skv, Dh), dtype)
    v = jnp.array(RNG.randn(B, Hk, Skv, Dh), dtype)
    kw = dict(causal=causal, window=window, q_offset=q_offset)
    if blocks is not None:
        kw.update(block_q=blocks[0], block_k=blocks[1])
    if entry == "fwd":
        out = flash_attention_fwd(q, k, v, interpret=True, **kw)
    else:
        out, lse = flash_attention_fwd_lse(q, k, v, interpret=True, **kw)
        assert lse.shape == (B, H, Sq, 1) and lse.dtype == jnp.float32
        want = _masked_logsumexp(q, k, causal=causal, window=window, q_offset=q_offset)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=1e-4, rtol=1e-5)
    ref = attention_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


@pytest.mark.parametrize(
    "B,H,Hk,Sq,Skv,q_offset,Dh,Dv,causal,window,blocks,dtype",
    [
        (2, 4, 2, 256, 256, 0, 64, 64, True, None, None, jnp.float32),
        # window smaller than one block, and larger than a block
        (1, 2, 2, 256, 256, 0, 64, 64, True, 32, (64, 64), jnp.float32),
        (1, 2, 2, 256, 256, 0, 64, 64, True, 96, (64, 32), jnp.float32),
        # the last row that sees a k-block opens the next q-block
        (1, 2, 2, 256, 256, 0, 64, 64, True, 66, (64, 64), jnp.float32),
        # Sq != Skv at a static offset, GQA group 4, block_q != block_k
        (1, 4, 1, 128, 512, 384, 64, 64, True, None, (64, 128), jnp.float32),
        # offsets off the block grid; keys past the last query (no query
        # sees them: their dk/dv rows are zero)
        (1, 4, 4, 128, 384, 97, 64, 64, True, 96, (32, 64), jnp.float32),
        (1, 2, 2, 128, 256, 94, 64, 64, True, None, (32, 64), jnp.float32),
        # keys before the first query's window
        (1, 2, 2, 64, 256, 192, 64, 64, True, 32, (32, 32), jnp.float32),
        # no causal mask: every tile runs; with a window, a lower bound only
        (2, 2, 2, 256, 256, 0, 64, 64, False, None, (128, 64), jnp.float32),
        (1, 2, 2, 256, 256, 0, 64, 64, False, 63, (64, 64), jnp.float32),
        (1, 8, 2, 256, 256, 0, 64, 64, True, None, (64, 128), jnp.bfloat16),
        (2, 4, 4, 256, 256, 0, 64, 64, True, None, None, jnp.bfloat16),
        # latent attention's widths: queries and keys 192, values 128
        (1, 4, 4, 256, 256, 0, 192, 128, True, None, None, jnp.float32),
    ],
)
def test_flash_attention_bwd_sweep(
    B, H, Hk, Sq, Skv, q_offset, Dh, Dv, causal, window, blocks, dtype
):
    """dq, dk and dv of ``flash_attention_bwd`` against the gradients of
    the oracle (interpret mode)."""
    q = jnp.array(RNG.randn(B, H, Sq, Dh), dtype)
    k = jnp.array(RNG.randn(B, Hk, Skv, Dh), dtype)
    v = jnp.array(RNG.randn(B, Hk, Skv, Dv), dtype)
    do = jnp.array(RNG.randn(B, H, Sq, Dv), dtype)
    kw = dict(causal=causal, window=window, q_offset=q_offset)
    o, lse = flash_attention_fwd_lse(q, k, v, interpret=True, **kw)
    bkw = {} if blocks is None else dict(block_q=blocks[0], block_k=blocks[1])
    got = flash_attention_bwd(q, k, v, o, lse, do, interpret=True, **kw, **bkw)
    f32 = lambda x: x.astype(jnp.float32)
    _, vjp = jax.vjp(lambda q, k, v: attention_ref(q, k, v, **kw), f32(q), f32(k), f32(v))
    want = vjp(f32(do))
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    for name, g, r, x in zip("qkv", got, want, (q, k, v)):
        assert g.shape == x.shape and g.dtype == x.dtype, name
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        np.testing.assert_allclose(g, r, atol=tol * np.abs(r).max(), err_msg=name)
    kpos = np.arange(Skv)
    seen = np.ones(Skv, bool)
    if causal:
        seen &= kpos <= q_offset + Sq - 1
    if window is not None:
        seen &= kpos > q_offset - window
    for g in got[1:]:
        assert not np.asarray(g)[:, :, ~seen].any()


@pytest.mark.parametrize(
    "Sq,Skv,q_offset,block_q,block_k,causal",
    [
        (64, 64, 0, 16, 16, True),
        (64, 64, 0, 32, 8, True),
        (64, 64, 0, 8, 32, True),
        (32, 96, 41, 8, 16, True),
        (32, 96, 64, 16, 8, True),
        (64, 64, 0, 16, 8, False),
        (48, 48, 0, 16, 16, False),
    ],
)
def test_kv_block_range_matches_the_mask(Sq, Skv, q_offset, block_q, block_k, causal):
    """For every window up to twice a block: a tile outside the range has
    no unmasked (query, key) pair; every tile inside it has at least one."""
    qpos = np.arange(Sq)[:, None] + q_offset
    kpos = np.arange(Skv)[None, :]
    for window in [None, *range(1, 2 * max(block_q, block_k) + 3)]:
        mask = np.ones((Sq, Skv), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        for iq in range(Sq // block_q):
            lo, hi = kv_block_range(
                iq, causal=causal, window=window, q_offset=q_offset,
                block_q=block_q, block_k=block_k, kv_len=Skv,
            )
            for ik in range(Skv // block_k):
                tile = mask[iq * block_q:(iq + 1) * block_q, ik * block_k:(ik + 1) * block_k]
                assert bool(tile.any()) == (int(lo) <= ik <= int(hi)), (window, iq, ik)


@pytest.mark.parametrize(
    "Sq,Skv,q_offset,block_q,block_k,causal",
    [
        (64, 64, 0, 16, 16, True),
        (64, 64, 0, 32, 8, True),
        (64, 64, 0, 8, 32, True),
        (32, 96, 41, 8, 16, True),
        (32, 96, 64, 16, 8, True),
        (32, 128, 40, 16, 16, True),
        (64, 64, 0, 16, 8, False),
        (48, 48, 0, 16, 16, False),
    ],
)
def test_q_block_range_matches_the_mask(Sq, Skv, q_offset, block_q, block_k, causal):
    """For every window up to twice a block: a tile outside the range has
    no unmasked (query, key) pair, every tile inside it has at least one,
    and a k-block no query sees has an empty range."""
    qpos = np.arange(Sq)[:, None] + q_offset
    kpos = np.arange(Skv)[None, :]
    for window in [None, *range(1, 2 * max(block_q, block_k) + 3)]:
        mask = np.ones((Sq, Skv), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        for ik in range(Skv // block_k):
            lo, hi = q_block_range(
                ik, causal=causal, window=window, q_offset=q_offset,
                block_q=block_q, block_k=block_k, q_len=Sq,
            )
            for iq in range(Sq // block_q):
                tile = mask[iq * block_q:(iq + 1) * block_q, ik * block_k:(ik + 1) * block_k]
                assert bool(tile.any()) == (int(lo) <= iq <= int(hi)), (window, iq, ik)


@pytest.mark.parametrize("block,share", [(128, 0.515625), (256, 0.53125), (512, 0.5625)])
def test_visited_share_at_4k(block, share):
    """Causal self-attention at S 4,096: the share of tiles the forward runs."""
    got = visited_share(
        q_len=4096, kv_len=4096, causal=True, window=None, q_offset=0,
        block_q=block, block_k=block,
    )
    assert got == share


@pytest.mark.parametrize("kernel,blocks,share", [
    ("dq", (1024, 512), 0.625),
    ("dkv", (512, 1024), 0.625),
    ("dkv", (512, 512), 0.5625),
    ("dkv", (128, 128), 0.515625),
])
def test_bwd_visited_share_at_4k(kernel, blocks, share):
    """Causal self-attention at S 4,096: the share of tiles each backward
    kernel runs; at equal blocks it is the forward's."""
    got = visited_share(
        q_len=4096, kv_len=4096, causal=True, window=None, q_offset=0,
        block_q=blocks[0], block_k=blocks[1], kernel=kernel,
    )
    assert got == share


# (q_len, kv_len, head_dim, v_dim): the qwen3-8b.train-4k and
# moonlight-16b-a3b.train-8k benchmark cells' attention, bf16
@pytest.mark.parametrize("shape", [(4096, 4096, 128, 128), (8192, 8192, 192, 128)])
def test_bwd_block_sizes_divide_and_fit(shape):
    q_len, kv_len, head_dim, v_dim = shape
    for kernel, (bq, bk) in zip(("dq", "dkv"), bwd_block_sizes(q_len, kv_len, head_dim, 2, v_dim)):
        assert q_len % bq == 0 and kv_len % bk == 0
        assert bq >= 128 and bk >= 128
        assert _bwd_vmem_bytes(bq, bk, head_dim, 2, v_dim, kernel=kernel) <= VMEM_BUDGET


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


@pytest.mark.parametrize("B,H,Hk,S,Dqk,Dv,causal", [
    (1, 4, 4, 256, 192, 128, True),     # latent attention's widths (Moonlight)
    (2, 4, 2, 128, 48, 32, True),
    (1, 2, 1, 256, 64, 128, False),
])
def test_flash_attention_value_width_differs(B, H, Hk, S, Dqk, Dv, causal):
    """Queries and keys of one width, values of another: the forward and
    all three gradients against the oracle (interpret mode)."""
    ks = jax.random.split(jax.random.PRNGKey(S + Dqk), 4)
    q = jax.random.normal(ks[0], (B, S, H, Dqk), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hk, Dqk), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hk, Dv), jnp.float32)
    w = jax.random.normal(ks[3], (B, S, H, Dv), jnp.float32)
    scale = Dqk ** -0.5

    def ref(q, k, v):
        t = lambda x: jnp.swapaxes(x, 1, 2)
        return t(attention_ref(t(q), t(k), t(v), causal=causal, scale=scale))

    out = flash_attention(q, k, v, causal=causal, scale=scale)
    assert out.shape == (B, S, H, Dv)
    np.testing.assert_allclose(out, ref(q, k, v), atol=2e-5, rtol=2e-5)
    loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * w)
    got = jax.grad(loss(lambda q, k, v: flash_attention(q, k, v, causal=causal, scale=scale)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


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

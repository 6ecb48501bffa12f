"""The held-expert layer's pair gather and gated combine
(``kernels/moe_dispatch``), Pallas kernels in interpret mode: forward and
VJP against the jnp path at no, some and all pairs held; rows past the
held pairs never reach a result, NaN or not; and the layer's TPU path
(kernels interpreted, megablox ``gmm`` interpreted) against its CPU path
and the benchmark's plain reference."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_dispatch import moe_dispatch as md
from repro.kernels.moe_dispatch import ops
from repro.models import moe
from repro.models.common import DTypes

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"

# 64 tokens, top-3 of 5 groups (4 held, group 4 is not held), d 256:
# 192 pair rows in tiles of 64
T, K, D, GROUPS = 64, 3, 256, 5
N = T * K


def _pairs(seed, held_case):
    """(order, pos (T, K), sizes (4,), held): pairs sorted stably by
    group, the held ones first, with ``held`` none of them, a count off
    the row tile, or all."""
    if held_case == "all":
        grp = jax.random.randint(jax.random.PRNGKey(seed), (N,), 0, GROUPS - 1)
    else:
        grp = jax.random.randint(jax.random.PRNGKey(seed), (N,), 0, GROUPS)
    if held_case == "none":
        grp = jnp.full((N,), GROUPS - 1)
    order = jnp.argsort(grp, stable=True)
    sizes = jnp.bincount(grp, length=GROUPS)[:-1].astype(jnp.int32)
    return order, jnp.argsort(order).reshape(T, K), sizes, jnp.sum(sizes)


def _plain_dispatch(x, order, held):
    return jnp.where((jnp.arange(N) < held)[:, None], x[order // K], 0)


def _plain_combine(y, gates, pos, held):
    terms = y[pos].astype(jnp.float32) * gates[..., None]
    return jnp.sum(jnp.where((pos < held)[..., None], terms, 0.0), axis=1).astype(y.dtype)


@pytest.mark.parametrize("held_case", ["none", "partial", "all"])
def test_dispatch_and_combine_kernels_match_the_jnp_path(held_case):
    """Forward: the gather's rows below ``held`` (zero in the rest of the
    last tile) and the combine's tokens equal the jnp path's.  VJP of
    combine(dispatch(x) * w + y): dx, dw, dy below ``held`` and the gate
    gradient equal plain jnp autodiff's, to f32 rounding."""
    order, pos, sizes, held = _pairs(11, held_case)
    h, tile = int(held), md.row_tile(N, D * 2)
    if held_case == "partial":
        assert 0 < h < N and h % tile
    else:
        assert h == {"none": 0, "all": N}[held_case]
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (T, D)).astype(jnp.bfloat16)
    y = jax.random.normal(ks[1], (N, D)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[2], (N, D)).astype(jnp.bfloat16)
    gates = jax.random.uniform(ks[3], (T, K), minval=0.1, maxval=1.0)

    rows = ops.dispatch(x, order, pos, sizes, "kernel")
    want = _plain_dispatch(x, order, held)
    np.testing.assert_array_equal(np.asarray(rows[:h]), np.asarray(want[:h]))
    assert not np.asarray(rows[h:h + (-h) % tile]).any()
    for impl in ("kernel", "ref"):
        np.testing.assert_array_equal(np.asarray(ops.combine(y, gates, order, pos, sizes, impl)),
                                      np.asarray(_plain_combine(y, gates, pos, held)))

    def loss(dispatch, combine):
        def f(x, w, y, gates):
            out = combine(dispatch(x) * w + y, gates)
            return jnp.sum(jnp.sin(out.astype(jnp.float32)))
        return jax.grad(f, argnums=(0, 1, 2, 3))(x, w, y, gates)

    got = loss(lambda x: ops.dispatch(x, order, pos, sizes, "kernel"),
               lambda y, g: ops.combine(y, g, order, pos, sizes, "kernel"))
    ref = loss(lambda x: _plain_dispatch(x, order, held),
               lambda y, g: _plain_combine(y, g, pos, held))
    names = ("dx", "dw", "dy", "dgates")
    for name, a, b in zip(names, got, ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if name in ("dw", "dy"):
            a, b = a[:h], b[:h]
        np.testing.assert_allclose(a, b, rtol=1e-2, atol=1e-2, err_msg=name)
        assert np.isfinite(a).all(), name
    if h == 0:
        assert not np.asarray(got[0]).any() and not np.asarray(got[3]).any()


# ---------------------------------------------------------------------------
# the layer: moe_ffn_held's TPU path with the kernels interpreted
# ---------------------------------------------------------------------------

LT, LD, LF, LE, LK = 128, 128, 128, 8, 3


def _layer(held, first, shared=1):
    cfg = moe.MoEConfig(d_model=LD, d_ff=LF, num_experts=LE, top_k=LK,
                        num_shared_experts=shared, scoring="sigmoid", routed_scale=2.446,
                        held_experts=held, first_held=first)
    return cfg, DTypes(jnp.float32, jnp.float32)


def _layer_params(seed, held, first, starve=False, shared=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda key, shape, fan: jax.random.normal(key, shape) * fan ** -0.5
    router = n(k[0], (LD, LE), LD)
    if starve:   # every token scores the held experts lowest
        router = router.at[:, first:first + held].set(-1.0)
    sf = LF * shared
    return {"router": {"w": router},
            "wi": n(k[1], (held, LD, LF), LD), "wg": n(k[2], (held, LD, LF), LD),
            "wo": n(k[3], (held, LF, LD), LF),
            "shared": {"wi": {"w": n(k[4], (LD, sf), LD)}, "wg": {"w": n(k[5], (LD, sf), LD)},
                       "wo": {"w": n(k[6], (sf, LD), sf)}}}


def _layer_input(seed, starve=False):
    x = jax.random.normal(jax.random.PRNGKey(100 + seed), (2, LT // 2, LD))
    return jnp.abs(x) + 0.1 if starve else x


def _layer_grads(p, cfg, dt, x):
    def f(p, x):
        out, _, st = moe.moe_ffn(p, cfg, x, dt)
        return jnp.sum(jnp.sin(out)), (out, st)
    (_, (out, st)), g = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(p, x)
    return out, st, g


@jax.custom_vjp
def _nan_past(a, n):
    """Rows of ``a`` from ``n`` on set to NaN, and so the cotangent's."""
    return jnp.where((jnp.arange(a.shape[0]) < n)[:, None], a, jnp.nan)


_nan_past.defvjp(lambda a, n: (_nan_past(a, n), n),
                 lambda n, g: (jnp.where((jnp.arange(g.shape[0]) < n)[:, None], g, jnp.nan), None))


@pytest.mark.parametrize("case", ["some held", "all held", "none held"])
def test_rows_past_the_held_pairs_never_reach_a_result(case, monkeypatch):
    """On the TPU path every grouped product's input and output rows past
    the held pairs, and their cotangents, are NaN: the layer's output and
    every gradient stay finite and equal the CPU path's, which has none."""
    held, first, starve = {"some held": (2, 2, False), "all held": (LE, 0, False),
                           "none held": (2, 2, True)}[case]
    cfg, dt = _layer(held, first)
    p, x = _layer_params(3, held, first, starve), _layer_input(3, starve)
    want_out, want_st, want_g = _layer_grads(p, cfg, dt, x)

    grouped = moe._grouped

    def poisoned(x, w, sizes):
        n = jnp.sum(sizes)
        return _nan_past(grouped(_nan_past(x, n), w, sizes), n)

    monkeypatch.setattr(moe, "_on_cpu", lambda: False)
    monkeypatch.setattr(moe, "_grouped", poisoned)
    out, st, g = _layer_grads(p, cfg, dt, x)
    n = int(st["moe_tokens_held"])
    assert n == int(want_st["moe_tokens_held"])
    if case == "some held":
        assert 0 < n < LT * LK
    else:
        assert n == {"all held": LT * LK, "none held": 0}[case]
    np.testing.assert_allclose(out, want_out, atol=2e-5, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g), jax.tree_util.tree_leaves(want_g)):
        assert np.isfinite(np.asarray(a)).all(), jax.tree_util.keystr(path)
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_the_tpu_path_matches_the_cpu_path_and_the_reference(compute, monkeypatch):
    """moe_ffn_held with ``_on_cpu`` patched (Pallas gather and combine,
    megablox ``gmm``, all interpreted) against the CPU path (jnp gather
    and combine, ``ragged_dot``), output and every gradient; in f32 also
    against ``references/mla_moe.moe``."""
    monkeypatch.syspath_prepend(str(BENCH))
    from references import mla_moe as ref

    cfg, _ = _layer(2, 2, shared=2)
    dt = DTypes(jnp.float32, jnp.dtype(compute))
    p, x = _layer_params(5, 2, 2, shared=2), _layer_input(5)
    cpu_out, cpu_st, cpu_g = _layer_grads(p, cfg, dt, x)
    with monkeypatch.context() as mp:
        mp.setattr(moe, "_on_cpu", lambda: False)
        out, st, g = _layer_grads(p, cfg, dt, x)
    assert int(st["moe_tokens_held"]) == int(cpu_st["moe_tokens_held"]) > 0
    tol = dict(atol=2e-5, rtol=1e-5) if compute == "float32" else dict(atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(cpu_out, np.float32), **tol)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g), jax.tree_util.tree_leaves(cpu_g)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   err_msg=jax.tree_util.keystr(path), **tol)
    if compute == "float32":
        conf = json.loads((BENCH / "tests" / "data" / "tiny-mla-moe.json").read_text())
        conf = dict(conf, hidden_size=LD, moe_intermediate_size=LF, router_experts=LE,
                    n_routed_experts=2, first_held_expert=2, num_experts_per_tok=LK,
                    n_shared_experts=2)
        want = ref.moe(ref.F32, ref.dims(conf), p, x)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-5)

"""The held-expert layer's dispatch and combine as one custom_vjp pair.

``dispatch`` gathers the rows of the (token, expert) pairs routed to held
experts, in expert order; ``combine`` sums each token's pairs back,
weighted by their gates.  Each is the other's transpose:

* ``dispatch``'s gradient is ``combine`` with unit gates: each token sums
  its pairs' rows in f32.
* ``combine``'s gradient is ``dispatch`` with each pair's gate as the
  scale, which gives the rows' gradient, and from the same pass the
  gate's gradient ``<dout[token], y[pair]>`` in f32, unsorted to (T, K)
  by a scalar gather.

``impl`` picks the Pallas kernels (``"kernel"``: interpreted on the CPU,
compiled on the TPU) or the jnp oracle (``"ref"``).  The pairs are
sorted stably by expert, the held experts first: ``order`` (T*K,) holds
the flat (token * K + slot) index of each sorted pair, ``pos`` (T, K) is
its inverse, and ``sizes`` the held experts' pair counts, whose sum
``held`` is the count of pairs the layer computes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import moe_dispatch as md
from .ref import combine_ref, gather_ref


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _gather_kernel(x, order, pos, sizes, scale=None, dot_with=None):
    return md.gather_rows(x, order // pos.shape[1], jnp.sum(sizes), scale, dot_with,
                          interpret=_on_cpu())


def _combine_kernel(y, order, pos, sizes, gates=None):
    interpret = _on_cpu()
    y3 = md.relayout_rows(y, jnp.sum(sizes), interpret=interpret)
    return md.combine_rows(y3, order, pos, sizes, gates, interpret=interpret)


_IMPLS = {"kernel": (_gather_kernel, _combine_kernel), "ref": (gather_ref, combine_ref)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def dispatch(x, order, pos, sizes, impl):
    """(T*K, D): row i is ``x[order[i] // K]`` for the held pairs i."""
    gather, _ = _IMPLS[impl]
    return gather(x, order, pos, sizes)


def _dispatch_fwd(x, order, pos, sizes, impl):
    return dispatch(x, order, pos, sizes, impl), (order, pos, sizes)


def _dispatch_bwd(impl, res, g):
    _, combine = _IMPLS[impl]
    return combine(g, *res), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def combine(y, gates, order, pos, sizes, impl):
    """(T, D): token t's ``sum_k gates[t, k] y[pos[t, k]]`` over its held
    pairs, in f32, stored in ``y.dtype``."""
    _, comb = _IMPLS[impl]
    return comb(y, order, pos, sizes, gates)


def _combine_fwd(y, gates, order, pos, sizes, impl):
    return combine(y, gates, order, pos, sizes, impl), (y, gates, order, pos, sizes)


def _combine_bwd(impl, res, dout):
    y, gates, order, pos, sizes = res
    gather, _ = _IMPLS[impl]
    dy, dgate = gather(dout, order, pos, sizes, gates.reshape(-1)[order], y)
    dgates = jnp.where(pos < jnp.sum(sizes), dgate[pos], 0.0)
    return dy, dgates, None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)

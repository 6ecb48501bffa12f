"""The model's flash-attention path against the jnp reference attention.

``attn_impl="flash"`` runs the Pallas kernels (interpret mode on the CPU)
in place of the reference einsum.  One training step's loss and gradients
must agree on both paths the trainer can take: no mesh in context (the
kernel is called directly) and under a mesh (a shard_map island).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models.model_zoo import get_model
from repro.parallel.sharding import make_rules, use_rules

# Both paths compute in f32 and differ only in the order of the softmax
# sums (online softmax over key blocks vs one pass), so they agree to f32
# rounding (2^-24 relative per operation) accumulated over two layers.
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4


def _loss_and_grads(cfg, params, batch, mesh):
    """(loss, grads, whether the traced step holds a Pallas kernel)."""
    zoo = get_model(cfg)

    def step(p, b):
        loss_grad = jax.value_and_grad(lambda p: zoo.loss(p, b)[0])
        if mesh is None:
            return loss_grad(p)
        with use_rules(make_rules(mesh.axis_names), mesh):
            return loss_grad(p)

    has_kernel = "pallas_call" in str(jax.make_jaxpr(step)(params, batch))
    return (*jax.jit(step)(params, batch), has_kernel)


def _tree_rel_err(a, b) -> float:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    num = sum(float(jnp.sum((x - y) ** 2)) for x, y in zip(la, lb))
    den = sum(float(jnp.sum(y**2)) for y in lb)
    return float(np.sqrt(num / den))


@pytest.mark.parametrize("with_mesh", [False, True], ids=["no_mesh", "mesh"])
def test_flash_train_step_matches_ref(with_mesh):
    cfg = get_smoke_config("qwen3-8b")
    mesh = make_mesh((1,), ("data",)) if with_mesh else None
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab, (2, 65)).astype(np.int32)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    loss_f, g_f, kernel_f = _loss_and_grads(
        dataclasses.replace(cfg, attn_impl="flash"), params, batch, mesh
    )
    loss_r, g_r, kernel_r = _loss_and_grads(
        dataclasses.replace(cfg, attn_impl="ref"), params, batch, mesh
    )
    assert kernel_f and not kernel_r
    assert abs(float(loss_f) - float(loss_r)) <= LOSS_RTOL * abs(float(loss_r))
    assert _tree_rel_err(g_f, g_r) <= GRAD_RTOL
    # the attention weights' gradients flow through the backward kernels
    assert _tree_rel_err(g_f["layers"]["attn"], g_r["layers"]["attn"]) <= GRAD_RTOL


def test_flash_refuses_sliding_window():
    """A windowed layer has no flash path; asking for one is an error, not
    a silent fall back to the reference."""
    cfg = dataclasses.replace(get_smoke_config("gemma3-4b"), attn_impl="flash")
    zoo = get_model(cfg)
    params = zoo.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="sliding-window"):
        zoo.forward(params, {"tokens": tokens})

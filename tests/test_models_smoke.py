"""Per-arch reduced-config smoke tests (assignment deliverable f):
one forward + train-ish loss + two decode steps on CPU; asserts output
shapes and no NaNs for every assigned architecture."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALL_CONFIGS, ARCHS, get_smoke_config
from repro.models.model_zoo import get_model


def _batch(cfg, B=2, S=16):
    key = jax.random.PRNGKey(7)
    batch = {
        "tokens": jnp.arange(B * S).reshape(B, S) % cfg.vocab,
        "targets": (jnp.arange(B * S).reshape(B, S) + 1) % cfg.vocab,
    }
    if cfg.family == "vlm":
        batch["positions3"] = jnp.broadcast_to(
            jnp.arange(S)[None, None], (3, B, S)
        )
    if cfg.family == "whisper":
        batch["enc_embeds"] = jax.random.normal(key, (B, S, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_loss(arch):
    cfg = get_smoke_config(arch)
    zoo = get_model(cfg)
    params = zoo.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = zoo.forward(params, batch)
    assert logits.shape == (2, 16, cfg.vocab)
    assert not bool(jnp.isnan(logits).any())
    loss, metrics = zoo.loss(params, batch)
    assert jnp.isfinite(loss)
    if cfg.moe is not None and cfg.moe.aux_loss_coeff > 0:
        assert float(aux) > 0.0  # aux loss active
    if cfg.moe is not None and cfg.moe.held_experts is not None:
        # a held share counts the pairs routed to it
        assert 0 < int(metrics["moe_tokens_held"]) <= (cfg.num_layers - cfg.first_dense_layers) * 32 * cfg.moe.top_k


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_steps(arch):
    cfg = get_smoke_config(arch)
    zoo = get_model(cfg)
    params = zoo.init(jax.random.PRNGKey(0))
    B = 2
    if cfg.mla is not None:
        # the latent cache is not implemented: decoding refuses, plainly
        with pytest.raises(NotImplementedError, match="latent"):
            zoo.init_cache(B, 32)
        with pytest.raises(NotImplementedError, match="latent"):
            zoo.decode_step(params, {}, {"tokens": jnp.zeros((B, 1), jnp.int32)})
        return
    cache = zoo.init_cache(B, 32)
    if cfg.family == "whisper":
        cache["enc_out"] = jax.random.normal(
            jax.random.PRNGKey(1), cache["enc_out"].shape
        )
    db = {"tokens": jnp.zeros((B, 1), jnp.int32)}
    if cfg.family == "vlm":
        db["positions3"] = jnp.zeros((3, B, 1), jnp.int32)
    lg1, cache = zoo.decode_step(params, cache, db)
    lg2, cache = zoo.decode_step(params, cache, db)
    assert lg1.shape == (B, 1, cfg.vocab)
    assert not bool(jnp.isnan(lg2).any())
    assert int(cache["index"]) == 2


@pytest.mark.parametrize("arch", ["llama3.2-3b", "qwen3-moe-235b-a22b", "gemma3-4b"])
def test_decode_matches_forward(arch):
    """Teacher-forced decode step-by-step must match the parallel forward."""
    cfg = get_smoke_config(arch)
    zoo = get_model(cfg)
    params = zoo.init(jax.random.PRNGKey(0))
    B, S = 1, 8
    batch = _batch(cfg, B, S)
    logits, _ = zoo.forward(params, batch)
    cache = zoo.init_cache(B, S)
    outs = []
    for t in range(S):
        db = {"tokens": batch["tokens"][:, t : t + 1]}
        lg, cache = zoo.decode_step(params, cache, db)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    # MoE capacity dispatch differs between batch/step routing; compare
    # argmax agreement for MoE, values for dense
    if cfg.moe is None:
        assert jnp.allclose(dec, logits, atol=2e-2), float(
            jnp.abs(dec - logits).max()
        )
    else:
        agree = jnp.mean(
            (jnp.argmax(dec, -1) == jnp.argmax(logits, -1)).astype(jnp.float32)
        )
        assert agree > 0.7


def test_param_counts_documented():
    """The 6ND accounting used for rooflines matches actual param trees."""
    import numpy as np

    for arch in ["qwen3-8b", "llama3.2-3b"]:
        cfg = get_smoke_config(arch)
        zoo = get_model(cfg)
        params = zoo.init(jax.random.PRNGKey(0))
        actual = sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
        est = cfg.param_count()
        assert abs(actual - est) / actual < 0.25, (arch, actual, est)


@pytest.mark.parametrize("impl", ["dense", "held"])
def test_moonlight_router_on_every_path(impl):
    """The published configuration's router (64 experts, sigmoid scores,
    top-6, gates normalised over the top-6 and scaled by 2.446, 2 shared
    experts), at a width a CPU holds, on the capacity path (capacity
    wide enough that no token drops) and on the held path holding every
    expert: both give the layer computed by hand from those gates."""
    import dataclasses

    import numpy as np

    from repro.configs import get_config
    from repro.models.common import DTypes, swiglu
    from repro.models.moe import init_moe, moe_ffn
    from repro.models.transformer import _moe_cfg

    full = get_config("moonlight-16b-a3b")
    assert (full.moe.num_experts, full.moe.top_k, full.moe.scoring, full.moe.routed_scale,
            full.moe.num_shared_experts, full.moe.held_experts) == (64, 6, "sigmoid", 2.446, 2, None)
    moe = dataclasses.replace(full.moe, d_ff=16, capacity_factor=64 / 6,
                              held_experts=64 if impl == "held" else None)
    cfg = _moe_cfg(dataclasses.replace(full, d_model=32, moe=moe))
    dt = DTypes(jnp.float32, jnp.float32)
    p = init_moe(jax.random.PRNGKey(0), cfg, dt)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    out, _, _ = moe_ffn(p, cfg, x, dt, impl="dense")

    xt = np.asarray(x.reshape(-1, 32), np.float64)
    s = 1 / (1 + np.exp(-xt @ np.asarray(p["router"]["w"], np.float64)))
    top = np.argsort(-s, axis=-1)[:, :6]
    gates = np.take_along_axis(s, top, -1)
    gates = 2.446 * gates / gates.sum(-1, keepdims=True)
    w = {k: np.asarray(p[k], np.float64) for k in ("wi", "wg", "wo")}
    want = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        for e, g in zip(top[t], gates[t]):
            h = xt[t] @ w["wg"][e]
            want[t] += g * ((h / (1 + np.exp(-h))) * (xt[t] @ w["wi"][e])) @ w["wo"][e]
    want += np.asarray(swiglu(p["shared"], x.reshape(-1, 32), dt), np.float64)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 32), want, atol=1e-4, rtol=1e-4)

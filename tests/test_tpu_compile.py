"""The flash-attention kernels compile for a TPU v5e at qwen3-8b widths,
and the program's scopes and kernel names reach the compiled TPU HLO.

Interpret mode never checks the TPU lowering's tiling rules, so these
tests compile each kernel with ``interpret=False`` for a described (not
attached) ``v5e:2x2`` topology and look for the Mosaic custom call in the
compiled HLO.  Nothing runs.  The topology is described inside a fixture:
only the test worker that is handed this file loads the TPU library.
"""

import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_bwd,
    flash_attention_fwd,
    flash_attention_fwd_lse,
)

# qwen3-8b attention: 32 query heads of 128; the model broadcasts its 8 KV
# heads to the query heads before the kernel (group 1), the kernel itself
# also takes them grouped (group 4).
B, H, S, DH = 1, 32, 2048, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call")


# The kernels compile at the blocks ``fwd_block_sizes`` and
# ``bwd_block_sizes`` pick for each shape, so a working set past VMEM fails
# here; (2, 4,096) is the qwen3-8b.train-4k benchmark cell's.
SHAPES = [(B, S), (2, 4096)]


@pytest.mark.parametrize("batch,seq", SHAPES)
@pytest.mark.parametrize("group", [1, 4])
def test_flash_fwd_compiles(group, batch, seq, one_chip, no_persistent_cache):
    q = _sds((batch, H, seq, DH), jnp.bfloat16, one_chip)
    kv = _sds((batch, H // group, seq, DH), jnp.bfloat16, one_chip)
    n = _kernel_calls(lambda q, k, v: flash_attention_fwd(q, k, v, interpret=False), q, kv, kv)
    assert n >= 1


@pytest.mark.parametrize("batch,seq", SHAPES)
@pytest.mark.parametrize("group", [1, 4])
def test_flash_fwd_lse_compiles(group, batch, seq, one_chip, no_persistent_cache):
    q = _sds((batch, H, seq, DH), jnp.bfloat16, one_chip)
    kv = _sds((batch, H // group, seq, DH), jnp.bfloat16, one_chip)
    n = _kernel_calls(
        lambda q, k, v: flash_attention_fwd_lse(q, k, v, interpret=False), q, kv, kv
    )
    assert n >= 1


@pytest.mark.parametrize("batch,seq", SHAPES)
@pytest.mark.parametrize("group", [1, 4])
def test_flash_bwd_compiles(group, batch, seq, one_chip, no_persistent_cache):
    q = _sds((batch, H, seq, DH), jnp.bfloat16, one_chip)
    kv = _sds((batch, H // group, seq, DH), jnp.bfloat16, one_chip)
    lse = _sds((batch, H, seq, 1), jnp.float32, one_chip)
    n = _kernel_calls(
        lambda q, k, v, o, lse, do: flash_attention_bwd(
            q, k, v, o, lse, do, interpret=False
        ),
        q, kv, kv, q, lse, q,
    )
    assert n >= 2  # the dq kernel and the dk/dv kernel


# ---------------------------------------------------------------------------
# named scopes and kernel names in the compiled step programs
# ---------------------------------------------------------------------------

import hlo_scopes as scopes  # noqa: E402

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"

# head_dim 128 and 256 tokens: the widths the kernels' TPU tiling takes
TINY = dict(name="tiny", family="dense", num_layers=2, d_model=256, heads=2, kv_heads=1,
            head_dim=128, d_ff=512, vocab=512, qk_norm=True, tie_embeddings=False,
            param_dtype=jnp.float32, compute_dtype=jnp.bfloat16, remat=True, attn_impl="flash")
TB, TS, CACHE = 2, 256, 512


def _shapes(tree, shardings):
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)


@pytest.fixture(scope="module")
def compiled_steps(topo, no_persistent_cache):
    """The compiled HLO text of a tiny dense model's train step
    (``make_train_step``) and decode step (``make_serve_step``) for one
    described chip, with the flash kernels compiled, not interpreted."""
    from repro.configs.base import ModelConfig
    from repro.kernels.flash_attention import ops
    from repro.models.model_zoo import get_model
    from repro.serve.serve_step import make_serve_step
    from repro.train import optimizer as opt_lib
    from repro.train.train_step import make_train_step

    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    zoo = get_model(ModelConfig(**TINY))
    ocfg = opt_lib.AdamWConfig()
    params = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0)))
    example = {"tokens": np.zeros((TB, TS), np.int32), "targets": np.zeros((TB, TS), np.int32)}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_cpu", lambda: False)
        arts = make_train_step(zoo, ocfg, mesh, example)
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=arts.batch_sharding[k])
                 for k, v in example.items()}
        train = arts.step_fn.lower(
            _shapes(params, arts.param_sharding),
            _shapes(jax.eval_shape(lambda p: opt_lib.init(ocfg, p), params), arts.opt_sharding),
            batch).compile().as_text()
    cache = jax.eval_shape(lambda: zoo.init_cache(TB, CACHE))
    sarts = make_serve_step(zoo, mesh, {"tokens": np.zeros((TB, 1), np.int32)}, cache_example=cache)
    decode = sarts.decode_fn.lower(
        _shapes(params, sarts.param_sharding), _shapes(cache, sarts.cache_sharding),
        {"tokens": jax.ShapeDtypeStruct((TB, 1), jnp.int32, sharding=NamedSharding(mesh, P()))},
    ).compile().as_text()
    return {"train": train, "decode": decode}


def test_every_kernel_call_carries_its_name(compiled_steps, monkeypatch):
    """Every kernel call names its kernel, and the benchmark's
    ``flash_kind``, which tells the flash kernels apart by signature,
    agrees with the name."""
    monkeypatch.syspath_prepend(str(BENCH))
    from harness.reduce import flash_kind

    kinds = []
    for ins in scopes.instructions(compiled_steps["train"]):
        if 'custom_call_target="tpu_custom_call"' not in ins.text:
            continue
        name = scopes.kernel_of(ins.op_name)
        assert name is not None, ins.op_name
        assert name == f"flash_{flash_kind(ins.text)}", (name, ins.text[:200])
        assert scopes.scope_of(ins.op_name) == "attention", ins.op_name
        kinds.append(name)
    # forward, its recomputation under remat, and both backward kernels
    assert sorted(set(kinds)) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd_lse"]


@pytest.mark.parametrize("step,want", [
    ("train", {"attention", "mlp", "head"}),
    ("decode", {"attention", "mlp", "head", "kv_cache"}),
])
def test_every_matmul_is_under_a_scope(compiled_steps, step, want):
    """Every dot (a ``convolution`` on the TPU) sits under one of the
    program's scopes, and the layers' products are all found."""
    found = set()
    for ins in scopes.instructions(compiled_steps[step]):
        if ins.opcode in ("dot", "convolution"):
            scope = scopes.scope_of(ins.op_name)
            assert scope is not None, (ins.op_name, ins.text[:200])
            found.add(scope)
    assert found == want


def test_decode_cache_write_is_under_kv_cache(compiled_steps):
    """The step's K/V write into the cache is the ``kv_cache`` scope's; the
    scan's stacking of the layers' caches is ``layers``'s."""
    found = {scopes.scope_of(ins.op_name) for ins in scopes.instructions(compiled_steps["decode"])
             if ins.opcode == "dynamic-update-slice"}
    assert found == {"kv_cache", "layers"}


def test_train_step_has_each_layer_scope(compiled_steps):
    found = {scopes.scope_of(ins.op_name) for ins in scopes.instructions(compiled_steps["train"])}
    assert {"embed", "layers", "attention", "mlp", "head", "optimizer"} <= found


# ---------------------------------------------------------------------------
# moonlight-16b-a3b: latent attention's widths and the held experts
# ---------------------------------------------------------------------------

# the benchmark cell's shapes: 2 x 8,192 tokens, 16 heads, queries and keys
# of 192, values of 128; 8 held experts of 1,408 over d_model 2,048, the
# (token, expert) pairs of top-6 routing (98,304 rows) in 9 groups (the
# last holds the pairs of experts that are not held)
MLA_B, MLA_H, MLA_S, MLA_DQK, MLA_DV = 2, 16, 8192, 192, 128


@pytest.mark.parametrize("entry", ["fwd_lse", "bwd"])
def test_flash_compiles_at_latent_attention_widths(entry, one_chip, no_persistent_cache):
    q = _sds((MLA_B, MLA_H, MLA_S, MLA_DQK), jnp.bfloat16, one_chip)
    v = _sds((MLA_B, MLA_H, MLA_S, MLA_DV), jnp.bfloat16, one_chip)
    lse = _sds((MLA_B, MLA_H, MLA_S, 1), jnp.float32, one_chip)
    if entry == "fwd_lse":
        n = _kernel_calls(lambda q, k, v: flash_attention_fwd_lse(q, k, v, interpret=False), q, q, v)
        assert n >= 1
    else:
        n = _kernel_calls(
            lambda q, k, v, o, lse, do: flash_attention_bwd(q, k, v, o, lse, do, interpret=False),
            q, q, v, v, lse, v,
        )
        assert n >= 2


def test_held_expert_products_compile(one_chip, no_persistent_cache):
    """The grouped products of one MoE layer, forward and backward
    (``gmm`` and ``tgmm``), at the tiles ``_gmm_tiling`` picks."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from repro.models.moe import _gmm_tiling

    x = _sds((98304, 2048), jnp.bfloat16, one_chip)
    w = _sds((8, 2048, 1408), jnp.bfloat16, one_chip)
    wo = _sds((8, 1408, 2048), jnp.bfloat16, one_chip)
    sizes = _sds((9,), jnp.int32, one_chip)

    def f(x, w, wo, sizes):
        h = gmm(x, w, sizes, jnp.bfloat16, _gmm_tiling)
        return jnp.sum(jnp.sin(gmm(h, wo, sizes, jnp.bfloat16, _gmm_tiling).astype(jnp.float32)))

    n = _kernel_calls(jax.grad(f, argnums=(0, 1, 2)), x, w, wo, sizes)
    assert n >= 6   # two forward products; each backward: gmm and tgmm


TINY_MLA = dict(name="tiny-mla", family="moe", num_layers=2, d_model=256, heads=2, kv_heads=2,
                d_ff=512, vocab=512, tie_embeddings=False, rms_norm_eps=1e-5,
                first_dense_layers=1,
                mla={"kv_lora_rank": 128, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                     "v_head_dim": 128},
                moe={"num_experts": 8, "top_k": 2, "d_ff": 128, "num_shared_experts": 1,
                     "scoring": "sigmoid", "routed_scale": 2.446, "held_experts": 4,
                     "aux_loss_coeff": 0.0},
                param_dtype=jnp.float32, compute_dtype=jnp.bfloat16, remat=True, attn_impl="flash")


@pytest.fixture(scope="module")
def compiled_moe_step(topo, no_persistent_cache):
    """The compiled HLO text of a tiny latent-attention, held-expert
    model's train step for one described chip, kernels compiled."""
    from repro.configs.base import ModelConfig
    from repro.kernels.flash_attention import ops
    from repro.kernels.moe_dispatch import ops as dispatch_ops
    from repro.models import moe
    from repro.models.model_zoo import get_model
    from repro.train import optimizer as opt_lib
    from repro.train.train_step import make_train_step

    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    zoo = get_model(ModelConfig(**TINY_MLA))
    ocfg = opt_lib.AdamWConfig()
    params = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0)))
    example = {"tokens": np.zeros((TB, TS), np.int32), "targets": np.zeros((TB, TS), np.int32)}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_cpu", lambda: False)
        mp.setattr(moe, "_on_cpu", lambda: False)
        mp.setattr(dispatch_ops, "_on_cpu", lambda: False)
        arts = make_train_step(zoo, ocfg, mesh, example)
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=arts.batch_sharding[k])
                 for k, v in example.items()}
        return arts.step_fn.lower(
            _shapes(params, arts.param_sharding),
            _shapes(jax.eval_shape(lambda p: opt_lib.init(ocfg, p), params), arts.opt_sharding),
            batch).compile().as_text()


def _sub_scopes(op_name: str) -> set:
    return {scopes._unwrap(p) for p in op_name.split("/")}


def test_moe_step_kernels_and_sub_scopes(compiled_moe_step):
    """The flash kernels sit under ``attention``; the grouped products and
    the pair gather and combine under ``moe``/``moe_experts``, forward and
    backward, so ``expert_roofline_pct.train`` counts their time; the step
    has the sub-scopes the benchmark's readers take (``moe_route``,
    ``moe_experts``, ``moe_shared``, ``mla_kv``), and they count under
    their scope.  No op under ``moe_experts`` writes an f32 buffer of every
    (token, expert) pair, (T*K, D) or (T, K, D)."""
    T, K, D = TB * TS, TINY_MLA["moe"]["top_k"], TINY_MLA["d_model"]
    full_f32 = {f"f32[{T * K},{D}]", f"f32[{T},{K},{D}]"}
    kernels, subs, forward, backward = set(), {}, set(), set()
    for ins in scopes.instructions(compiled_moe_step):
        parts = _sub_scopes(ins.op_name)
        for sub in ("moe_route", "moe_experts", "moe_shared", "mla_kv"):
            if sub in parts:
                subs.setdefault(sub, set()).add(scopes.scope_of(ins.op_name))
        if "moe_experts" in parts:
            shapes = set(re.findall(r"f32\[[\d,]*\]", ins.shape))
            assert not shapes & full_f32, ins.text[:300]
        if 'custom_call_target="tpu_custom_call"' not in ins.text:
            continue
        name = scopes.kernel_of(ins.op_name)
        kernels.add(name)
        want = "attention" if name.startswith("flash_") else "moe"
        assert scopes.scope_of(ins.op_name) == want, ins.op_name
        if want == "moe":
            assert "moe_experts" in parts, ins.op_name
            (backward if "/transpose(" in ins.op_name else forward).add(name)
    assert {"flash_fwd_lse", "flash_bwd_dq", "flash_bwd_dkv", "gmm", "tgmm",
            "moe_gather", "moe_combine"} <= kernels
    assert {"gmm", "moe_gather", "moe_combine"} <= forward
    assert {"gmm", "tgmm", "moe_gather", "moe_combine"} <= backward
    assert subs == {"moe_route": {"moe"}, "moe_experts": {"moe"}, "moe_shared": {"moe"},
                    "mla_kv": {"attention"}}

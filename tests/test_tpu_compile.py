"""The flash-attention kernels compile for a TPU v5e at qwen3-8b widths.

Interpret mode never checks the TPU lowering's tiling rules, so these
tests compile each kernel with ``interpret=False`` for a described (not
attached) ``v5e:2x2`` topology and look for the Mosaic custom call in the
compiled HLO.  Nothing runs.  The topology is described inside a fixture:
only the test worker that is handed this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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


@pytest.mark.parametrize("group", [1, 4])
def test_flash_fwd_compiles(group, one_chip, no_persistent_cache):
    q = _sds((B, H, S, DH), jnp.bfloat16, one_chip)
    kv = _sds((B, H // group, S, DH), jnp.bfloat16, one_chip)
    n = _kernel_calls(lambda q, k, v: flash_attention_fwd(q, k, v, interpret=False), q, kv, kv)
    assert n >= 1


@pytest.mark.parametrize("group", [1, 4])
def test_flash_fwd_lse_compiles(group, one_chip, no_persistent_cache):
    q = _sds((B, H, S, DH), jnp.bfloat16, one_chip)
    kv = _sds((B, H // group, S, DH), jnp.bfloat16, one_chip)
    n = _kernel_calls(
        lambda q, k, v: flash_attention_fwd_lse(q, k, v, interpret=False), q, kv, kv
    )
    assert n >= 1


@pytest.mark.parametrize("group", [1, 4])
def test_flash_bwd_compiles(group, one_chip, no_persistent_cache):
    q = _sds((B, H, S, DH), jnp.bfloat16, one_chip)
    kv = _sds((B, H // group, S, DH), jnp.bfloat16, one_chip)
    lse = _sds((B, H, S, 1), jnp.float32, one_chip)
    n = _kernel_calls(
        lambda q, k, v, o, lse, do: flash_attention_bwd(
            q, k, v, o, lse, do, interpret=False
        ),
        q, kv, kv, q, lse, q,
    )
    assert n >= 2  # the dq kernel and the dk/dv kernel

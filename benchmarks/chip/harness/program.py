"""The system under test as the benchmark drives it: its user entry points
``get_model`` -> ``make_train_step`` / ``make_serve_step``.  This is the
only module of the benchmark that imports the program (``src/repro``).
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Any, Dict, Sequence

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import ModelConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402,F401
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.model_zoo import get_model  # noqa: E402
from repro.serve.serve_step import make_serve_step  # noqa: E402
from repro.train import optimizer as opt_lib  # noqa: E402
from repro.train.train_step import make_train_step  # noqa: E402


def model_config(name: str, kwargs: Dict[str, Any], settings: Dict[str, Any]) -> ModelConfig:
    return ModelConfig(
        name=name, **kwargs,
        param_dtype=jnp.dtype(settings["param_dtype"]),
        compute_dtype=jnp.dtype(settings["compute_dtype"]),
        remat=bool(settings.get("remat", False)),
        attn_impl=settings.get("attn_impl", "ref"),
    )


def mesh(shape: Sequence[int], axes: Sequence[str]):
    return make_mesh(tuple(shape), tuple(axes))


def train(cfg: ModelConfig, optimizer: Dict[str, Any], mesh_, batch_example,
          dp_mode: str, schedule: str):
    """(model, step artifacts, optimizer config, optimizer-state init)."""
    zoo = get_model(cfg)
    ocfg = opt_lib.AdamWConfig(**optimizer)
    arts = make_train_step(zoo, ocfg, mesh_, batch_example, dp_mode=dp_mode, schedule=schedule)
    init_opt = jax.jit(lambda p: opt_lib.init(ocfg, p), out_shardings=arts.opt_sharding)
    return zoo, arts, ocfg, init_opt


def serve(cfg: ModelConfig, mesh_, batch: int, cache_len: int):
    zoo = get_model(cfg)
    import numpy as np

    arts = make_serve_step(
        zoo, mesh_, {"tokens": np.zeros((batch, 1), np.int32)},
        cache_example=jax.eval_shape(lambda: zoo.init_cache(batch, cache_len)))
    return zoo, arts


def param_shapes(zoo):
    """Shapes of the program's parameter tree (nothing is allocated)."""
    shapes = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(lambda a: tuple(a.shape), shapes)

"""Model dispatch: one uniform API over the four model classes.

    zoo = get_model(cfg)            # cfg.family decides the class
    params = zoo.init(key)
    logits, aux = zoo.forward(params, batch)
    cache = zoo.init_cache(batch_size, cache_len)
    logits, cache = zoo.decode_step(params, cache, batch)
    specs = zoo.param_specs()       # logical-axis tree for sharding
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import hybrid, transformer, whisper, xlstm_lm


@dataclasses.dataclass(frozen=True)
class ModelZoo:
    cfg: ModelConfig
    _mod: Any

    def init(self, key):
        return self._mod.init(key, self.cfg)

    def forward(self, params, batch):
        return self._mod.forward(params, self.cfg, batch)

    def param_specs(self):
        return self._mod.param_specs(self.cfg)

    def init_cache(self, batch: int, cache_len: int):
        return self._mod.init_cache(self.cfg, batch, cache_len)

    def cache_specs(self):
        return self._mod.cache_specs(self.cfg)

    def decode_step(self, params, cache, batch):
        return self._mod.decode_step(params, self.cfg, cache, batch)

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Next-token cross entropy over batch['targets'] with optional
        batch['loss_mask']; adds MoE aux loss.  The metrics carry the
        model's counters where it has them (``forward_with_stats``)."""
        with_stats = getattr(self._mod, "forward_with_stats", None)
        if with_stats is not None:
            logits, aux, stats = with_stats(params, self.cfg, batch)
        else:
            (logits, aux), stats = self.forward(params, batch), {}
        with jax.named_scope("head"):
            targets = batch["targets"]
            logits32 = logits.astype(jnp.float32)
            logz = jax.nn.logsumexp(logits32, axis=-1)
            gold = jnp.take_along_axis(logits32, targets[..., None], axis=-1)[..., 0]
            nll = logz - gold
            mask = batch.get("loss_mask")
            if mask is None:
                loss = jnp.mean(nll)
            else:
                loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        total = loss + aux
        return total, {"nll": loss, "aux": aux, **stats}


_FAMILIES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "xlstm": xlstm_lm,
    "hybrid": hybrid,
    "whisper": whisper,
}


def get_model(cfg: ModelConfig) -> ModelZoo:
    if cfg.family not in _FAMILIES:
        raise KeyError(f"unknown model family {cfg.family!r}")
    return ModelZoo(cfg, _FAMILIES[cfg.family])

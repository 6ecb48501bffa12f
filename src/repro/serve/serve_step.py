"""Serving steps: prefill + batched decode with sharded KV caches.

``make_serve_step`` builds the jitted one-token decode (the dry-run's
``serve_step`` for decode_32k / long_500k cells) and ``make_prefill_step``
the full-context forward that also writes the cache.  Cache sharding
follows the model's logical cache specs (batch over DP axes, kv_heads over
the TP axis — KV is replicated within a TP group's head shard).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.model_zoo import ModelZoo
from ..parallel.sharding import logical_spec_tree, make_rules, use_rules
from ..train.train_step import batch_specs_tree


@dataclasses.dataclass(frozen=True)
class ServeArtifacts:
    decode_fn: Callable
    prefill_fn: Optional[Callable]
    param_sharding: Any
    cache_sharding: Any
    rules: Any


def make_serve_step(
    zoo: ModelZoo,
    mesh: Mesh,
    batch_example: Dict[str, Any],
    rules_overrides: Optional[Dict[str, Any]] = None,
    cache_example: Optional[Any] = None,
) -> ServeArtifacts:
    rules = make_rules(tuple(mesh.shape.keys()), rules_overrides)
    from ..train.train_step import sanitize_specs

    pspecs = logical_spec_tree(zoo.param_specs(), rules)
    pspecs = sanitize_specs(
        pspecs, jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0))), mesh
    )
    param_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
    cspecs = logical_spec_tree(zoo.cache_specs(), rules)
    if cache_example is not None:
        cspecs = sanitize_specs(cspecs, cache_example, mesh)
    cache_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), cspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
    bspecs = batch_specs_tree(mesh, batch_example)
    batch_sharding = {k: NamedSharding(mesh, s) for k, s in bspecs.items()}

    def decode(params, cache, batch):
        with use_rules(rules, mesh):
            logits, new_cache = zoo.decode_step(params, cache, batch)
        return logits, new_cache

    jit_decode = jax.jit(
        decode,
        in_shardings=(param_sharding, cache_sharding, batch_sharding),
        out_shardings=(None, cache_sharding),
        donate_argnums=(1,),
    )

    def prefill(params, batch):
        with use_rules(rules, mesh):
            logits, _ = zoo.forward(params, batch)
        return logits

    jit_prefill = jax.jit(prefill, in_shardings=(param_sharding, batch_sharding))

    return ServeArtifacts(jit_decode, jit_prefill, param_sharding, cache_sharding, rules)


# ---------------------------------------------------------------------------
# Minimal batched request scheduler (continuous batching flavor)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    rid: int
    prompt: Any                 # token array
    max_new: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


class BatchScheduler:
    """Greedy slot-based scheduler: fixed decode batch of ``slots``; new
    requests fill free slots; finished requests free them.  Drives the
    jitted decode step with a stable shape (production continuous
    batching reduced to its schedulable core)."""

    def __init__(self, slots: int, eos_id: int = 0):
        self.slots = slots
        self.eos_id = eos_id
        self.active: Dict[int, Request] = {}
        self.queue: list[Request] = []

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def admit(self) -> list[Request]:
        admitted = []
        while self.queue and len(self.active) < self.slots:
            req = self.queue.pop(0)
            free = next(i for i in range(self.slots) if i not in self.active)
            self.active[free] = req
            admitted.append(req)
        return admitted

    def step_tokens(self, sampled: Any) -> None:
        """sampled: (slots,) int array of new tokens for each slot."""
        for slot, req in list(self.active.items()):
            tok = int(sampled[slot])
            req.generated.append(tok)
            if tok == self.eos_id or len(req.generated) >= req.max_new:
                req.done = True
                del self.active[slot]

    @property
    def idle(self) -> bool:
        return not self.active and not self.queue

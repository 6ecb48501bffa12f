"""Batched serving example: prefill + continuous-batching decode with the
slot scheduler, on a (data, model) mesh with sharded KV caches.

  PYTHONPATH=src python examples/serve_decode.py
"""

import os

# a simulated 8-device host mesh: stay on the CPU even where a chip exists
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models.model_zoo import get_model
    from repro.serve.serve_step import BatchScheduler, Request, make_serve_step

    cfg = get_smoke_config("qwen3-8b")
    zoo = get_model(cfg)
    mesh = make_mesh((4, 2), ("data", "model"))
    SLOTS, CACHE = 4, 64

    params = zoo.init(jax.random.PRNGKey(0))
    batch_example = {"tokens": jnp.zeros((SLOTS, 1), jnp.int32)}
    arts = make_serve_step(
        zoo, mesh, batch_example,
        cache_example=jax.eval_shape(lambda: zoo.init_cache(SLOTS, CACHE)),
    )
    params = jax.device_put(params, arts.param_sharding)
    cache = jax.device_put(zoo.init_cache(SLOTS, CACHE), arts.cache_sharding)

    sched = BatchScheduler(slots=SLOTS, eos_id=1)
    rng = np.random.RandomState(0)
    for rid in range(6):
        sched.submit(Request(rid=rid, prompt=rng.randint(2, cfg.vocab, 4),
                             max_new=8))

    # simple greedy decode over slots; empty slots feed token 0
    tokens = jnp.zeros((SLOTS, 1), jnp.int32)
    steps = 0
    while not sched.idle and steps < 64:
        admitted = sched.admit()
        for req in admitted:
            # prefill-by-decode for brevity: feed the prompt token by token
            for t in req.prompt:
                slot = next(s for s, r in sched.active.items() if r is req)
                tokens = tokens.at[slot, 0].set(int(t))
        logits, cache = arts.decode_fn(params, cache, {"tokens": tokens})
        sampled = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        sched.step_tokens(sampled)
        tokens = jnp.asarray(sampled[:, None], jnp.int32)
        steps += 1

    done = 6 - len(sched.queue) - len(sched.active)
    print(f"decode steps: {steps}, requests completed: {done}/6")
    assert steps > 0 and done >= 4
    print("OK: batched serving works")


if __name__ == "__main__":
    main()

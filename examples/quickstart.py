"""Quickstart: the RailX toolkit in 60 seconds.

  PYTHONPATH=src python examples/quickstart.py

1. Design a RailX installation and configure its topology (paper §3).
2. Map a 5D-parallel LLM workload onto it (paper §5).
3. Estimate collective times with the analytical model (paper §4.2).
4. Run one training step of a small model with the paper's hierarchical
   collective schedule on a simulated 8-device mesh.
"""

import os

# a simulated 8-device host mesh: stay on the CPU even where a chip exists
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

from repro.core.analytical import t_allreduce_2d_ring, t_allreduce_hierarchical
from repro.core.cost import table3
from repro.core.mapping import (
    ModelSpec, ParallelismPlan, WorkloadShape, plan_dimension_split,
)
from repro.core.topology import RailXConfig, table2_metrics


def main():
    # 1. hardware + topology --------------------------------------------
    cfg = RailXConfig(m=4, n=9, R=128)
    print(f"RailX m={cfg.m} n={cfg.n} R={cfg.R}: {cfg.num_chips} chips, "
          f"{cfg.num_switches} OCSes")
    for name, row in table2_metrics(cfg).items():
        print(f"  {name:10s} scale={row['scale']:>10.0f} "
              f"diam={row['diameter_ho']:>3} bisect/chip={row['bisection_per_chip']:.2f}")
    rx = [r for r in table3() if r["name"] == "RailX7Mesh"][0]
    print(f"  cost: {rx['cost_musd']}M$ for {rx['scale']} chips "
          f"({rx['cost_per_inject_x']}x FT cost/injection)")

    # 2. workload mapping ------------------------------------------------
    model = ModelSpec(layers=80, hidden=8192, intermediate=28672,
                      vocab=128256, heads=64, kv_heads=8, experts=8, top_k=2)
    plan = ParallelismPlan(tp=16, cp=2, ep=8, dp=16, pp=4)
    shape = WorkloadShape(micro_batch=1, num_micro_batches=8, seq_len=8192)
    res = plan_dimension_split(RailXConfig(m=4, n=9, R=128), model, plan, shape)
    print("\ndimension split (rails per logical dim):")
    for s in res.specs:
        print(f"  {s.name:4s} phys={s.phys} scale={s.scale:<4d} rails={s.rails:<3d} {s.interconnect}")

    # 3. collective estimates ---------------------------------------------
    V, nB, alpha, k = 2 * 8192 * 28672 * 3 / 16, 9 * 100e9, 300e-9, 4.0
    ring = t_allreduce_2d_ring(4, 16, V, nB, alpha)
    hier = t_allreduce_hierarchical(4, 16, V, nB, alpha, k)
    print(f"\nDP grad all-reduce estimate: 2D-ring {ring*1e3:.2f} ms vs "
          f"hierarchical {hier*1e3:.2f} ms ({ring/hier:.2f}x)")

    # 4. one real training step -------------------------------------------
    from repro.configs import get_smoke_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models.model_zoo import get_model
    from repro.train import optimizer as opt_lib
    from repro.train.train_step import make_train_step
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg_m = get_smoke_config("llama3.2-3b")
    zoo = get_model(cfg_m)
    data = SyntheticLM(DataConfig(vocab=cfg_m.vocab, seq_len=32, global_batch=8))
    ocfg = opt_lib.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    dp_mode, schedule = "manual_hier", "hierarchical"
    arts = make_train_step(zoo, ocfg, mesh, data.batch(0),
                           dp_mode=dp_mode, schedule=schedule)
    p = jax.device_put(zoo.init(jax.random.PRNGKey(0)), arts.param_sharding)
    o = jax.device_put(opt_lib.init(ocfg, zoo.init(jax.random.PRNGKey(0))),
                       arts.opt_sharding)
    print(f"\ntraining 5 steps with dp_mode={dp_mode}:")
    for step in range(5):
        b = {k_: jax.device_put(v, arts.batch_sharding[k_])
             for k_, v in data.batch(step).items()}
        p, o, m = arts.step_fn(p, o, b)
        print(f"  step {step}: loss {float(m['loss']):.4f}")


if __name__ == "__main__":
    main()

"""Training launcher: mesh + mapping + train loop + fault tolerance.

Example (CPU, tiny):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \\
      --steps 50 --devices 8 --mesh 2,2,2 --axes pod,data,model

``--profile-dir DIR`` records the run with the JAX profiler: the device's
ops under the model's named scopes, and ``train_loop``'s host spans on the
same clock (a ``train`` step annotation per step, with ``train.data``,
``train.sync`` and ``train.checkpoint`` inside it), so a slow input, sync
or checkpoint save shows beside the steps.  ``DIR`` holds one
``*.xplane.pb`` (open it with XProf or TensorBoard's profile plugin, or
read it with ``jax.profiler.ProfileData.from_file``).
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true", help="use reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--devices", type=int, default=0,
                    help="force host device count (must be set before jax init)")
    ap.add_argument("--mesh", default="", help="e.g. 2,2,2")
    ap.add_argument("--axes", default="", help="e.g. pod,data,model")
    ap.add_argument("--dp-mode", default="gspmd_fsdp")
    ap.add_argument("--schedule", default="hierarchical")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--profile-dir", default="",
                    help="record the run with the JAX profiler into this directory")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}"
        )

    import jax

    from ..configs import get_config, get_smoke_config
    from ..data.pipeline import DataConfig, SyntheticLM
    from ..models.model_zoo import get_model
    from ..train import optimizer as opt_lib
    from ..train.train_step import make_train_step
    from ..train.trainer import CheckpointPolicy, StragglerMonitor, train_loop, resume
    from .compile_cache import enable_compile_cache
    from .mesh import make_mesh

    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    zoo = get_model(cfg)

    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        axes = tuple(args.axes.split(","))
    else:
        n = len(jax.devices())
        shape, axes = (n,), ("data",)
    mesh = make_mesh(shape, axes)
    print(f"mesh: {dict(mesh.shape)} devices={len(jax.devices())}")

    data = SyntheticLM(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                   global_batch=args.global_batch)
    )
    ocfg = opt_lib.AdamWConfig(
        lr=args.lr, warmup_steps=max(5, args.steps // 20), total_steps=args.steps
    )
    arts = make_train_step(
        zoo, ocfg, mesh, data.batch(0), dp_mode=args.dp_mode,
        schedule=args.schedule, microbatches=args.microbatches,
    )
    params = jax.device_put(zoo.init(jax.random.PRNGKey(0)), arts.param_sharding)
    opt = jax.device_put(opt_lib.init(ocfg, params), arts.opt_sharding)
    start = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointPolicy(args.ckpt_dir, every_steps=args.ckpt_every)
        if args.resume:
            params, opt, start = resume(
                args.ckpt_dir,
                jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params
                ),
                jax.eval_shape(lambda p: opt_lib.init(ocfg, p), params),
                shardings={"params": arts.param_sharding, "opt": arts.opt_sharding},
            )
            print(f"resumed at step {start}")

    def batches():
        step = start
        while True:
            b = data.batch(step)
            yield {
                k: jax.device_put(v, arts.batch_sharding[k]) for k, v in b.items()
            }
            step += 1

    profile = (jax.profiler.trace(args.profile_dir) if args.profile_dir
               else contextlib.nullcontext())
    with profile:
        res = train_loop(
            arts.step_fn, params, opt, batches(), num_steps=args.steps,
            start_step=start, ckpt=ckpt, straggler=StragglerMonitor(),
        )
    print(
        f"done: {res.steps_done} steps, final loss {res.last_metrics.get('loss'):.4f}"
    )


if __name__ == "__main__":
    main()

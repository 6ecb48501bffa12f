import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (assignment deliverable e).

For every (architecture x input-shape) cell and both production meshes
(16x16 single-pod, 2x16x16 multi-pod), lower + compile the train or serve
step from ShapeDtypeStruct stand-ins (no allocation), then record:

  * memory_analysis() per-device bytes (proves it fits),
  * cost_analysis() raw FLOPs/bytes,
  * the loop-corrected roofline terms from the compiled HLO
    (launch/roofline.py).

Results land in results/dryrun/<cell>.json; EXPERIMENTS.md tables are
generated from those files by benchmarks/collect_dryrun.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] ...
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import SHAPES, get_config, supports_long_context
from ..configs.base import ModelConfig, ShapeConfig
from ..models.model_zoo import get_model
from ..train.optimizer import AdamWConfig
from ..train.train_step import make_train_step
from ..serve.serve_step import make_serve_step
from . import roofline
from .mesh import make_production_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../results/dryrun")


def dryrun_model_config(cfg: ModelConfig) -> ModelConfig:
    """Deployment numerics: bf16 params+compute, remat on."""
    return dataclasses.replace(
        cfg, param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16, remat=True
    )


def input_specs(
    cfg: ModelConfig, shape: ShapeConfig
) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    f = cfg.compute_dtype
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        batch: Dict[str, Any] = {
            "tokens": sds((B, S), jnp.int32),
            "targets": sds((B, S), jnp.int32),
        }
        if cfg.family == "vlm":
            batch["embeds"] = sds((B, S, cfg.d_model), f)
            batch["positions3"] = sds((3, B, S), jnp.int32)
            del batch["tokens"]
        if cfg.family == "whisper":
            batch["enc_embeds"] = sds((B, S, cfg.d_model), f)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": sds((B, S), jnp.int32)}
        if cfg.family == "vlm":
            batch["embeds"] = sds((B, S, cfg.d_model), f)
            batch["positions3"] = sds((3, B, S), jnp.int32)
            del batch["tokens"]
        if cfg.family == "whisper":
            batch["enc_embeds"] = sds((B, S, cfg.d_model), f)
            batch["tokens"] = sds((B, S), jnp.int32)
        return batch
    # decode: one new token against a cache of length S
    batch = {"tokens": sds((B, 1), jnp.int32)}
    if cfg.family == "vlm":
        batch["positions3"] = sds((3, B, 1), jnp.int32)
    return batch


def cell_is_skipped(arch: str, shape_name: str) -> Optional[str]:
    if shape_name == "long_500k" and not supports_long_context(arch):
        return (
            "full-attention arch: long_500k requires sub-quadratic context "
            "(DESIGN.md §Shape-cell skips)"
        )
    return None


def _mem_dict(ma) -> Dict[str, float]:
    return {
        k: float(getattr(ma, k))
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        )
        if hasattr(ma, k)
    }


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    dp_mode: str = "gspmd_fsdp",
    schedule: str = "hierarchical",
    microbatches: int = 1,
    rules_overrides: Optional[Dict[str, Any]] = None,
    model_overrides: Optional[Dict[str, Any]] = None,
    tag: str = "",
) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    mesh_name = "pod2" if multi_pod else "pod1"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    skip = cell_is_skipped(arch, shape_name)
    if skip:
        return {"cell": cell_id, "status": "SKIP", "reason": skip}

    # remat/jit jaxpr caches key on function identity + avals and would
    # replay a constraint bound to the previous cell's mesh; dry-run cells
    # deliberately use different meshes in one process.
    jax.clear_caches()
    cfg = dryrun_model_config(get_config(arch))
    if model_overrides:
        cfg = dataclasses.replace(cfg, **model_overrides)
    zoo = get_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.perf_counter()

    batch_sds = input_specs(cfg, shape)
    params_sds = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0)))
    from ..parallel.sharding import attention_overrides

    overrides = dict(
        attention_overrides(cfg, mesh.shape.get("model", 1), shape.kind)
    )
    if shape.kind == "decode" and shape.global_batch < 32:
        # long-context decode: batch unshardable; context-parallel KV instead
        overrides.setdefault("batch", None)
        overrides.setdefault("kv_seq", "data")
    overrides.update(rules_overrides or {})

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        arts = make_train_step(
            zoo, opt_cfg, mesh, batch_sds,
            dp_mode=dp_mode, schedule=schedule, microbatches=microbatches,
            rules_overrides=overrides,
        )
        from ..train import optimizer as opt_lib

        opt_sds = jax.eval_shape(lambda p: opt_lib.init(opt_cfg, p), params_sds)
        lowered = arts.step_fn.lower(params_sds, opt_sds, batch_sds)
        tokens = shape.global_batch * shape.seq_len
        model_flops = roofline.model_train_flops(cfg.active_param_count(), tokens)
        default_trip = cfg.num_layers
    else:
        cache_sds = None
        if shape.kind == "decode":
            cache_sds = jax.eval_shape(
                lambda: zoo.init_cache(shape.global_batch, shape.seq_len)
            )
        arts = make_serve_step(
            zoo, mesh, batch_sds, rules_overrides=overrides,
            cache_example=cache_sds,
        )
        if shape.kind == "prefill":
            lowered = arts.prefill_fn.lower(params_sds, batch_sds)
            tokens = shape.global_batch * shape.seq_len
            model_flops = roofline.model_decode_flops(cfg.active_param_count(), tokens)
        else:
            lowered = arts.decode_fn.lower(params_sds, cache_sds, batch_sds)
            tokens = shape.global_batch * 1
            model_flops = roofline.model_decode_flops(cfg.active_param_count(), tokens)
        default_trip = cfg.num_layers

    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    hlo = compiled.as_text()
    extra_flops = 0.0
    if cfg.attn_impl == "flash":
        # attention FLOPs live inside the opaque kernel: QK^T and PV,
        # 2*B*H*S^2 times the query/key and the value width, halved for
        # causal; train = 4x (fwd + remat + bwd).
        B, S = shape.global_batch, shape.seq_len
        H, L = cfg.heads, cfg.num_layers
        fwd = 2 * B * H * S * S * sum(cfg.attn_widths) * 0.5 * L
        extra_flops = fwd * (4 if shape.kind == "train" else 1)
    report = roofline.build_report(
        arch, shape_name, mesh_name, chips, hlo, ca, _mem_dict(ma),
        model_flops, default_trip=default_trip, extra_flops_global=extra_flops,
    )
    out = {
        "cell": cell_id,
        "status": "OK",
        "dp_mode": dp_mode,
        "schedule": schedule,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "hlo_bytes": len(hlo),
        "report": report.as_dict(),
    }
    return out


def save_result(result: Dict[str, Any], out_dir: str = RESULTS_DIR) -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, result["cell"] + ".json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return path


def main() -> None:
    from ..configs import ARCHS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--dp-mode", default="gspmd_fsdp")
    ap.add_argument("--schedule", default="hierarchical")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--attn-impl", default="ref", help="ref|flash")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                t0 = time.perf_counter()
                try:
                    res = run_cell(
                        arch, shape, multi_pod=mp,
                        dp_mode=args.dp_mode, schedule=args.schedule,
                        microbatches=args.microbatches,
                        model_overrides=(
                            {"attn_impl": args.attn_impl}
                            if args.attn_impl != "ref" else None
                        ),
                        tag=args.tag,
                    )
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    res = {
                        "cell": f"{arch}__{shape}__{'pod2' if mp else 'pod1'}"
                        + (f"__{args.tag}" if args.tag else ""),
                        "status": "FAIL",
                        "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                path = save_result(res, args.out)
                status = res["status"]
                extra = ""
                if status == "OK":
                    r = res["report"]
                    extra = (
                        f" dom={r['dominant']} frac={r['roofline_fraction']:.3f}"
                        f" comp={r['compute_s']*1e3:.1f}ms"
                        f" mem={r['memory_s']*1e3:.1f}ms"
                        f" coll={r['collective_s']*1e3:.1f}ms"
                    )
                elif status == "FAIL":
                    extra = " " + res["error"][:120]
                print(
                    f"[{status}] {res['cell']} ({time.perf_counter()-t0:.0f}s){extra}",
                    flush=True,
                )
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()

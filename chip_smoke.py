#!/usr/bin/env python3
"""Bring-up smoke of the training and decode path on TPU.

  python chip_smoke.py               # one chip: train + decode
  python chip_smoke.py --four-chips  # four chips: RailX schedule vs FSDP only

Drives the entry points a user calls -- ``get_model`` -> ``make_train_step``
-> ``train_loop`` and ``make_serve_step`` -- at the published widths of
qwen3-8b, cut in depth and vocabulary to one chip's share (every cut is
printed with its published value), with random weights made from ``--seed``.
All phases run in this one process, which is the only one that touches the
chip, and each phase checks its own result: any failure exits non-zero.

One chip:
  * train: ~5 steps of the GSPMD FSDP step with the Pallas flash kernel;
    losses finite, the first within 0.5 nat of ln(vocab), the compiled step
    holds the kernel (``tpu_custom_call``), and flash agrees with the jnp
    reference attention on loss and gradients at the same parameters;
  * decode: prefill of a 128-token prompt agrees with feeding the same
    prompt token by token through the cached decode step.
Four chips (``--four-chips``): the ``manual_hier`` step with the paper's
hierarchical schedule (Eq. 8) against ``gspmd_fsdp`` on a (pod, data) =
(2, 2) mesh, same parameters and batches.

Informational lines come first.  Times printed are smoke figures of one
run (compilation reported apart), not benchmark measurements.  The last
line is one JSON object: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import math
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SEQ = 2048
TRAIN_BATCH = 4            # sequences per step on one chip
REF_BATCH = 1              # the jnp reference attention fits one sequence
TRAIN_STEPS = 5
DECODE_BATCH = 8
CACHE_LEN = 2048
PROMPT_LEN = 128
FOUR_CHIP_STEPS = 3

# Depth and vocabulary cut to one chip's share.  2 of 36 layers keeps f32
# master weights, gradients and f32 Adam moments (~16 B/param, ~8.7 GB)
# and the activations of 4 x 2048 tokens inside the 16 GB of one v5e: with
# each layer rematerialized in the backward pass the compiled step needs
# 12.7 GB; without, 17.5 GB.  18,992 ids is one chip's slice of an 8-way
# vocab-parallel 151,936-id head.
CUTS = {"num_layers": 2, "vocab": 18992}
SETTINGS = {"compute_dtype": "bfloat16", "param_dtype": "float32", "attn_impl": "flash",
            "remat": True}

# The optimizer as a real run starts: linear warmup over 2,000 steps to a
# peak of 3e-4.  At full rate from the first step Adam moves every weight
# by lr * sign(g) and the loss climbs (lr 1e-3 with no warmup went from
# 10.24 to 14.46 in 5 steps on one v5e).
OPT = {"lr": 3e-4, "warmup_steps": 2000, "total_steps": 100_000}

# Tolerances.  Both attention paths and both decode paths compute in bf16
# (8 significant bits, one rounding = 2^-8 ~ 0.4% relative) with f32
# softmax and accumulation; they round at different points, so they agree
# to a few bf16 roundings, while a wrong mask, scale, cache position or
# gradient gives errors of order 1 in the relative measures below.
INIT_LOSS_TOL = 0.5        # nats from ln(vocab) at random init
LOSS_TOL = 2e-2            # nats, flash vs reference on one sequence
GRAD_REL_TOL = 5e-2        # ||g_flash - g_ref|| / ||g_ref||, and grad norms
DECODE_REL_TOL = 5e-2      # rms(decode - prefill) / rms(prefill) logits
# manual_hier vs gspmd_fsdp: same math, different partitioning and
# reduction order in bf16 activations and f32 gradients.
MODES_LOSS_TOL = 2e-2      # nats, per step


def info(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def smoke_config():
    """qwen3-8b at published widths with CUTS and SETTINGS applied;
    prints each cut beside its published value."""
    import jax.numpy as jnp

    from repro.configs import get_config

    base = get_config("qwen3-8b")
    cfg = dataclasses.replace(
        base, name="qwen3-8b-chip-smoke", **CUTS,
        **{k: (getattr(jnp, v) if k.endswith("dtype") else v) for k, v in SETTINGS.items()},
    )
    info(
        f"config {base.name} published widths: d_model={cfg.d_model} "
        f"heads={cfg.heads} kv_heads={cfg.kv_heads} head_dim={cfg.resolved_head_dim} "
        f"d_ff={cfg.d_ff} qk_norm={cfg.qk_norm} rope_theta={cfg.rope_theta:g} "
        f"tie_embeddings={cfg.tie_embeddings}"
    )
    for k, v in CUTS.items():
        info(f"cut {k}: {v} (published {getattr(base, k)})")
    for k, v in SETTINGS.items():
        info(f"setting {k}: {v}")
    info(f"params: {cfg.param_count() / 1e6:.1f} M")
    return cfg


def _rel(a, b) -> float:
    return float(np.sqrt(np.sum((a - b) ** 2)) / max(np.sqrt(np.sum(b**2)), 1e-30))


def _tree_rel(ga, gb) -> float:
    import jax
    import jax.numpy as jnp

    num = sum(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
              for a, b in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)))
    den = sum(jnp.sum(jnp.square(b.astype(jnp.float32))) for b in jax.tree_util.tree_leaves(gb))
    return float(jnp.sqrt(num / den))


def compare_flash_ref(cfg, params, batch) -> dict:
    """Loss and gradients of the flash path vs the jnp reference attention
    at the same parameters (no mesh in context: the kernel is called
    directly)."""
    import jax

    from repro.models.model_zoo import get_model
    from repro.train.optimizer import global_norm

    out = {}
    for impl in ("flash", "ref"):
        zoo = get_model(dataclasses.replace(cfg, attn_impl=impl))
        loss_grad = jax.jit(jax.value_and_grad(lambda p, b, zoo=zoo: zoo.loss(p, b)[0]))
        loss, grads = loss_grad(params, batch)
        out[impl] = (float(loss), float(global_norm(grads)), grads)
    (lf, nf, gf), (lr, nr, gr) = out["flash"], out["ref"]
    res = {
        "loss_flash": lf, "loss_ref": lr, "gnorm_flash": nf, "gnorm_ref": nr,
        "grad_rel_err": _tree_rel(gf, gr),
        "attn_grad_rel_err": _tree_rel(gf["layers"]["attn"], gr["layers"]["attn"]),
    }
    info("flash vs ref (1 x %d): " % batch["tokens"].shape[1]
         + " ".join(f"{k}={v!r}" for k, v in res.items()))
    return res


def _put(batch, shardings):
    import jax

    return {k: jax.device_put(v, shardings[k]) for k, v in batch.items()}


def train_phase(cfg, mesh, *, seq, batch, ref_batch, steps, seed) -> dict:
    import jax

    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models.model_zoo import get_model
    from repro.train import optimizer as opt_lib
    from repro.train.train_step import make_train_step
    from repro.train.trainer import train_loop

    zoo = get_model(cfg)
    t0 = time.perf_counter()
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed))
    info(f"data: bigram table for {cfg.vocab} ids in {time.perf_counter() - t0:.1f} s (host)")
    ocfg = opt_lib.AdamWConfig(**OPT)
    arts = make_train_step(zoo, ocfg, mesh, data.batch(0), dp_mode="gspmd_fsdp")
    params = jax.device_put(zoo.init(jax.random.PRNGKey(seed)), arts.param_sharding)

    cmp = compare_flash_ref(cfg, params, {k: v[:ref_batch] for k, v in data.batch(0).items()})

    opt = jax.device_put(opt_lib.init(ocfg, params), arts.opt_sharding)
    t0 = time.perf_counter()
    step = arts.step_fn.lower(params, opt, _put(data.batch(0), arts.batch_sharding)).compile()
    compile_s = time.perf_counter() - t0
    kernels = step.as_text().count("tpu_custom_call")
    info(f"train step: compile {compile_s:.1f} s, tpu_custom_call in HLO: {kernels}")
    batches = (_put(data.batch(s), arts.batch_sharding) for s in itertools.count())
    res = train_loop(step, params, opt, batches, num_steps=steps, log_every=1, log_fn=info)
    del params, opt
    losses = [h["loss"] for h in res.history]
    step_s = [h["step_time_s"] for h in res.history]
    info(f"train losses: {losses} (ln vocab {math.log(cfg.vocab):.4f})")
    info(f"train step seconds (smoke timing, not a benchmark figure): {step_s}")
    return {"losses": losses, "kernels": kernels, "compile_s": compile_s, **cmp}


def decode_phase(cfg, mesh, *, batch, cache_len, prompt_len, seed) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.models.model_zoo import get_model
    from repro.serve.serve_step import make_serve_step

    zoo = get_model(cfg)
    arts = make_serve_step(
        zoo, mesh, {"tokens": np.zeros((batch, 1), np.int32)},
        cache_example=jax.eval_shape(lambda: zoo.init_cache(batch, cache_len)),
    )
    params = jax.device_put(zoo.init(jax.random.PRNGKey(seed + 1)), arts.param_sharding)
    prompt = np.random.RandomState(seed).randint(0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
    t0 = time.perf_counter()
    pre = np.asarray(arts.prefill_fn(params, {"tokens": prompt}), np.float32)
    prefill_s = time.perf_counter() - t0
    cache = jax.device_put(zoo.init_cache(batch, cache_len), arts.cache_sharding)
    outs = []
    t0 = time.perf_counter()
    logits, cache = arts.decode_fn(params, cache, {"tokens": prompt[:, :1]})
    outs.append(logits)
    jax.block_until_ready(logits)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for t in range(1, prompt_len):
        logits, cache = arts.decode_fn(params, cache, {"tokens": prompt[:, t : t + 1]})
        outs.append(logits)
    dec = np.asarray(jnp.concatenate(outs, axis=1), np.float32)
    rest_s = time.perf_counter() - t0
    check(int(cache["index"]) == prompt_len, "decode cache index")
    rel = _rel(dec, pre)
    max_abs = float(np.max(np.abs(dec - pre)))
    info(f"decode: batch {batch} cache {cache_len} prompt {prompt_len}; "
         f"rms rel err vs prefill {rel!r}, max abs err {max_abs!r}, "
         f"max |logit| {float(np.max(np.abs(pre)))!r}")
    info(f"decode seconds (smoke timing, compile included in the first call): "
         f"prefill {prefill_s:.2f}, first step {first_s:.2f}, "
         f"{prompt_len - 1} more steps {rest_s:.2f}")
    return {"decode_rel_err": rel, "finite": bool(np.isfinite(dec).all() and np.isfinite(pre).all())}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _groups(attr: str) -> frozenset:
    """Replica groups of an HLO collective, explicit ``{{0,1},{2,3}}`` or
    iota ``[2,2]<=[2,2]T(1,0)`` form, as a set of frozensets."""
    m = re.fullmatch(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", attr)
    if m:
        shape = [int(d) for d in m.group(1).split(",")]
        dims = [int(d) for d in m.group(2).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(3):
            ids = ids.transpose([int(d) for d in m.group(3).split(",")])
        rows = ids.reshape(shape[0], -1)
    else:
        rows = [[int(d) for d in g.split(",") if d] for g in re.findall(r"\{([\d,]*)\}", attr)]
    return frozenset(frozenset(int(d) for d in r) for r in rows)


def collectives(hlo: str) -> list:
    """(op, replica groups) of every all-reduce / reduce-scatter /
    all-gather in compiled HLO text."""
    pat = re.compile(
        r"\b(all-reduce|reduce-scatter|all-gather)(?:-start)?\(.*?replica_groups="
        r"(\{(?:\{[\d,]*\},?)*\}|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)"
    )
    return [(m.group(1), _groups(m.group(2))) for m in pat.finditer(hlo)]


def axis_groups(mesh, axis: str) -> frozenset:
    """Groups of partition ids that vary only along ``axis``."""
    ids = np.arange(mesh.devices.size).reshape(mesh.devices.shape)
    ids = np.moveaxis(ids, mesh.axis_names.index(axis), -1)
    return frozenset(frozenset(int(d) for d in r) for r in ids.reshape(-1, ids.shape[-1]))


def _spread(tree, devices) -> bool:
    import jax

    return all(
        {s.device for s in leaf.addressable_shards} == devices
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def four_chip_phase(cfg, mesh, *, seq, batch, steps, seed) -> dict:
    import jax

    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models.model_zoo import get_model
    from repro.train import optimizer as opt_lib
    from repro.train.train_step import make_train_step
    from repro.train.trainer import train_loop

    zoo = get_model(cfg)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed))
    ocfg = opt_lib.AdamWConfig(**OPT)
    host_params = jax.device_get(zoo.init(jax.random.PRNGKey(seed)))
    devices = set(mesh.devices.flat)
    out = {}
    for mode, schedule in (("gspmd_fsdp", "hierarchical"), ("manual_hier", "hierarchical")):
        arts = make_train_step(zoo, ocfg, mesh, data.batch(0), dp_mode=mode, schedule=schedule)
        params = jax.device_put(host_params, arts.param_sharding)
        opt = jax.device_put(opt_lib.init(ocfg, params), arts.opt_sharding)
        b0 = _put(data.batch(0), arts.batch_sharding)
        check(_spread(params, devices) and _spread(b0, devices),
              f"{mode}: a parameter or batch leaf is not on all {len(devices)} devices")
        t0 = time.perf_counter()
        step = arts.step_fn.lower(params, opt, b0).compile()
        compile_s = time.perf_counter() - t0
        hlo = step.as_text()
        colls = collectives(hlo)
        info(f"{mode}: compile {compile_s:.1f} s, tpu_custom_call {hlo.count('tpu_custom_call')}, "
             f"collectives {len(colls)}")
        batches = (_put(data.batch(s), arts.batch_sharding) for s in itertools.count())
        res = train_loop(step, params, opt, batches, num_steps=steps, log_every=1,
                         log_fn=lambda m, mode=mode: info(f"{mode} {m}"))
        del params, opt
        out[mode] = {"losses": [h["loss"] for h in res.history], "colls": colls,
                     "kernels": hlo.count("tpu_custom_call")}
    data_g, pod_g = axis_groups(mesh, "data"), axis_groups(mesh, "pod")
    colls = out["manual_hier"]["colls"]
    out["rs_ag_on_data"] = any(op in ("reduce-scatter", "all-gather") and g == data_g for op, g in colls)
    out["ar_on_pod"] = any(op == "all-reduce" and g == pod_g for op, g in colls)
    info(f"manual_hier collectives: RS/AG on data {out['rs_ag_on_data']}, AR on pod {out['ar_on_pod']}")
    info(f"losses gspmd_fsdp {out['gspmd_fsdp']['losses']} manual_hier {out['manual_hier']['losses']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip RailX-schedule comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devs)}", file=sys.stderr)
        return 1
    info(f"compile cache: {enable_compile_cache()}")
    info(f"device: {devs[0].device_kind} x {len(devs)}, jax {jax.__version__}")
    cfg = smoke_config()

    if args.four_chips:
        mesh = make_mesh((2, 2), ("pod", "data"))
        r = four_chip_phase(cfg, mesh, seq=SEQ, batch=TRAIN_BATCH, steps=FOUR_CHIP_STEPS,
                            seed=args.seed)
        a, b = r["gspmd_fsdp"]["losses"], r["manual_hier"]["losses"]
        check(all(math.isfinite(x) for x in a + b), "non-finite loss")
        check(all(abs(x - y) <= MODES_LOSS_TOL for x, y in zip(a, b)),
              f"manual_hier vs gspmd_fsdp losses differ by more than {MODES_LOSS_TOL}")
        check(r["manual_hier"]["kernels"] > 0, "no flash kernel in the manual_hier step")
        check(r["rs_ag_on_data"], "no reduce-scatter/all-gather on 'data' in the hierarchical step")
        check(r["ar_on_pod"], "no all-reduce on 'pod' in the hierarchical step")
    else:
        mesh = make_mesh((1,), ("data",))
        r = train_phase(cfg, mesh, seq=SEQ, batch=TRAIN_BATCH, ref_batch=REF_BATCH,
                        steps=TRAIN_STEPS, seed=args.seed)
        losses = r["losses"]
        check(len(losses) == TRAIN_STEPS and all(math.isfinite(x) for x in losses),
              f"train losses {losses}")
        check(abs(losses[0] - math.log(cfg.vocab)) <= INIT_LOSS_TOL,
              f"first loss {losses[0]} is not within {INIT_LOSS_TOL} of ln(vocab)")
        check(r["kernels"] > 0, "no tpu_custom_call in the compiled train step")
        check(abs(r["loss_flash"] - r["loss_ref"]) <= LOSS_TOL, "flash vs ref loss")
        check(abs(r["gnorm_flash"] - r["gnorm_ref"]) <= GRAD_REL_TOL * r["gnorm_ref"],
              "flash vs ref grad norm")
        check(r["grad_rel_err"] <= GRAD_REL_TOL and r["attn_grad_rel_err"] <= GRAD_REL_TOL,
              "flash vs ref gradients")
        stats = devs[0].memory_stats() or {}
        info(f"peak_bytes_in_use after train: {stats.get('peak_bytes_in_use')}")
        d = decode_phase(cfg, mesh, batch=DECODE_BATCH, cache_len=CACHE_LEN,
                         prompt_len=PROMPT_LEN, seed=args.seed)
        check(d["finite"], "non-finite decode or prefill logits")
        check(d["decode_rel_err"] <= DECODE_REL_TOL, "decode vs prefill logits")
        stats = devs[0].memory_stats() or {}
        info(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
             f"of bytes_limit {stats.get('bytes_limit')}")

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

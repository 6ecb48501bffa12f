"""Training cells of configurations with latent attention and a held
share of experts (``references/mla_moe.py``).

Set-up, the window and the reference are those of ``kinds/train.py``,
step for step (``first_steps`` and ``reference_readings`` take the same
steps and keep the first gradient besides).  ``correct`` takes ``compare.train_numbers`` and one
number more, ``grad_error``: over parameter leaves, the median of
||g - g_ref|| / ||g_ref|| of the first gradient, read (as the norms are)
from Adam's first moment after one step and held on the host, leaf by
leaf.  The norms' gaps are second order in an error of the gradient and
dominated here by routing choices that flip between bf16 and f32 near
top-k ties; this number is first order, so a matrix product in float8
stands out from the program's bf16.

What the per-layer readers get on a traced run also differs: model
FLOPs and flash costs from the configuration's own counts (queries and
keys of one width, values of another; the routed experts at the (token,
expert) pairs the step routed to held experts, its ``moe_tokens_held``
counter), the held experts' grouped products' cost, and device seconds
by the program's scopes (``harness/scopes.py``, as ``scope_time.py``
reads them) and by the sub-scopes ``SUB_SCOPES``, which count under
their scope there.
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import compare, data, scopes, weights, xplane
from ..manifest import Cell
from ..reduce import reduce
from . import common, train

SUB_SCOPES = ("moe_route", "moe_experts", "moe_shared", "mla_kv")


def _under(op_name: str, sub: str) -> bool:
    return any(scopes._unwrap(part) == sub for part in op_name.split("/"))


def scope_seconds(trace, names: Dict[str, str]):
    """(device seconds by scope, device seconds by sub-scope), all cores."""
    by_scope = scopes.device_time(trace, names)
    by_sub = {}
    for sub in SUB_SCOPES:
        only = {k: v for k, v in names.items() if _under(v, sub)}
        t = scopes.device_time(trace, only)
        by_sub[sub] = sum(s for k, s in t.items() if k != scopes.UNSCOPED)
    return by_scope, by_sub


def _host(tree, scale: float) -> List[np.ndarray]:
    """The leaves of ``tree`` on the host, in f32, times ``scale``."""
    return [np.asarray(jax.device_get(x), np.float32) * np.float32(scale)
            for x in jax.tree_util.tree_leaves(tree)]


def first_steps(su: train.Setup, seed: int, params, opt, batches):
    """``train.first_steps``, and the first gradient on the host."""
    step = su.arts.step_fn
    norms = jax.jit(su.ref.leaf_norms)
    change = jax.jit(lambda p, key: su.ref.change_norms(su.cell.config, key, p, su.pdtype))
    losses = []
    for i in range(su.first):
        params, opt, met = step(params, opt, batches[i])
        losses.append(met["loss"])
        if i == 0:
            mu = norms(opt.mu)
            gnorm = float(met["grad_norm"])
            scale = 1 / (1 - su.ocfg.b1) / min(1.0, su.ocfg.grad_clip / max(gnorm, 1e-12))
            first = _host(opt.mu, scale)
    grad = [float(x) * scale for x in mu]
    chg = [float(x) for x in change(params, weights.root_key(seed, weights.WEIGHTS))]
    readings = {"losses": [float(x) for x in losses], "grad": grad, "change": chg}
    return (params, opt, met), readings, first


def reference_readings(su: train.Setup, seed: int, fault: Optional[str] = None, fp8: bool = False):
    """``train.reference_readings``, and the first gradient on the host."""
    ref, conf = su.ref, su.cell.config
    opt = ref.AdamW(**conf["train"]["optimizer"])
    replicas = su.mesh.devices.size
    rep = NamedSharding(su.mesh, P())
    rows = NamedSharding(su.mesh, P(tuple(su.mesh.axis_names), None))
    rep_tree = jax.tree_util.tree_map(lambda _: rep, su.layout, is_leaf=weights.is_leaf)
    params = weights.make(su.layout, seed, jnp.float32, rep_tree)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p), out_shardings=rep_tree)
    mu, nu = zeros(params), zeros(params)
    batches = data.train_batches(seed, su.first, su.batch, su.seq, su.m.vocab, rows)
    step = ref.make_train_step(conf, opt, ref.FP8 if fp8 else ref.F32, fault=fault, replicas=replicas)
    losses = []
    for i in range(su.first):
        params, mu, nu, loss, gn = step(params, mu, nu, batches[i]["tokens"], batches[i]["targets"],
                                        jnp.int32(i + 1))
        losses.append(loss)
        if i == 0:
            grad = [float(x) for x in gn]
            clip = min(1.0, opt.grad_clip / max(math.sqrt(sum(g * g for g in grad)), 1e-12))
            first = _host(mu, 1 / (1 - opt.b1) / clip)
    del mu, nu, batches
    change = jax.jit(lambda p, key: ref.change_norms(conf, key, p, jnp.float32))
    chg = [float(x) for x in change(params, weights.root_key(seed, weights.WEIGHTS))]
    return {"losses": [float(x) for x in losses], "grad": grad, "change": chg}, first


def grad_error(got: List[np.ndarray], want: List[np.ndarray]) -> float:
    """Median over leaves of ||got - want|| / ||want||; inf if any leaf's
    is not finite (or ``want`` is zero there and ``got`` is not)."""
    errs = []
    for a, b in zip(got, want):
        num, den = float(np.linalg.norm(a - b)), float(np.linalg.norm(b))
        err = num / den if den > 0 else (0.0 if num == 0 else math.inf)
        if not math.isfinite(err):
            return math.inf
        errs.append(err)
    return float(np.median(errs))


def numbers(prog, prog_first, ref, ref_first) -> Dict[str, float]:
    return dict(compare.train_numbers(prog, ref), grad_error=grad_error(prog_first, ref_first))


def calibrate(cell: Cell, seeds, ctl: int, emit) -> None:
    """``calibrate.train_readings`` for this kind: for every seed the
    program's numbers; for the first ``ctl`` seeds also the float8
    control's, half of the batch left out, and a state left unchanged;
    each passed to ``emit`` as one dict."""
    su = train.build(cell)
    for i, seed in enumerate(seeds):
        params, opt, batches = train.start(su, seed)
        state, prog, prog_first = first_steps(su, seed, params, opt, batches)
        del state, params, opt, batches
        gc.collect()
        ref, ref_first = reference_readings(su, seed)
        emit({"seed": seed, "what": "program", **numbers(prog, prog_first, ref, ref_first)})
        del prog_first
        if i >= ctl:
            continue
        for what, kw in (("control_fp8", {"fp8": True}), ("half", {"fault": "half"})):
            got, got_first = reference_readings(su, seed, **kw)
            emit({"seed": seed, "what": what, **numbers(got, got_first, ref, ref_first)})
            del got_first
        still = {"losses": ref["losses"], "grad": [0.0] * len(ref["grad"]),
                 "change": [0.0] * len(ref["change"])}
        emit({"seed": seed, "what": "unchanged",
              **numbers(still, [np.zeros_like(b) for b in ref_first], ref, ref_first)})


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float) -> Dict:
    su = train.build(cell)
    params, opt, batches = train.start(su, seed)
    state, prog, prog_first = first_steps(su, seed, params, opt, batches)
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t_start
    tracer = common.Tracer() if trace else None
    if tracer:
        tracer.start()
    state, steps, win_s, gaps = train.window(su, state, batches, seconds)
    devices = list(su.mesh.devices.flat)
    mem = common.memory_peak(devices)
    trace_dir = tracer.stop() if tracer else None
    met = jax.device_get(state[2])
    held = int(met["moe_tokens_held"])
    hlo = (su.arts.step_fn.lower(state[0], state[1], batches[0]).compile().as_text()
           if trace_dir else None)
    del state, params, opt, batches
    gc.collect()

    ref, ref_first = reference_readings(su, seed)
    out = {
        "attempted": steps, "failed": 0, "numbers": numbers(prog, prog_first, ref, ref_first), "memory_peak_bytes": mem,
        "end_to_end": {
            "train_tokens_per_s": steps * su.batch * su.seq / win_s,
            "setup_s": setup_s,
        },
        "info": {"steps": steps, "window_s": win_s, "slowest_steps_s": sorted(gaps)[-3:],
                 "moe_tokens_held": held, "moe_max_load": float(met["moe_max_load"]),
                 "program": prog, "reference": ref},
    }
    if trace_dir:
        try:
            events = xplane.load(xplane.find(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if os.environ.get("BENCH_TRACE_DUMP"):
            xplane.dump(events, os.environ["BENCH_TRACE_DUMP"])
        by_scope, by_sub = scope_seconds(events, scopes.op_names(hlo))
        m, chips = su.m, len(devices)
        remat = bool(cell.config["train"].get("remat", False))
        out["info"].update(scope_s=by_scope, sub_scope_s=by_sub)
        out["trace"] = {
            "kind": "train", "chips": chips, "peak": common.peak_of(devices), "steps": steps,
            "train_step_flops": su.ref.train_step_flops(m, su.batch, su.seq, held),
            "flash_cost": {k: su.ref.flash_call(k, m, su.batch // chips, su.seq)
                           for k in ("fwd", "fwd_lse", "bwd_dq", "bwd_dkv")},
            "expert_cost": su.ref.expert_cost(m, held, remat),
            "scope_s": by_scope, "sub_scope_s": by_sub,
            "reduced": reduce(events),
        }
    return out

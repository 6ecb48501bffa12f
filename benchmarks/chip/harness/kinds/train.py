"""Training cells.

Set-up builds one object, the compiled step of ``make_train_step`` with
its state, makes weights and batches on the device from the seed, and
drives it through the mix's first steps (``first_steps``) with the
window's own call and feed.  From those steps it keeps the losses, each
leaf's norm of the first gradient as the optimizer received it (read from
Adam's first moment after one step) and each leaf's norm of the change
over the first steps.  The same object then runs the window: steps are
dispatched with one step in flight (the loop waits for the previous
step's loss, as a training loop that logs it does) until the time so far
and the median step so far reach the window's length; the window ends
when the last step's loss is ready (a slow step does not end it early).

After the window the program's state is freed and the plain reference
follows the same first steps from the same seed; ``compare`` decides
``correct``.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import compare, counts, data, program, weights
from ..manifest import Cell, reference
from . import common


class Setup(NamedTuple):
    cell: Cell
    ref: Any
    m: Any                  # reference Dims
    zoo: Any
    arts: Any
    ocfg: Any
    init_opt: Any
    mesh: Any
    layout: Any
    pdtype: Any
    batch: int
    seq: int
    first: int


def build(cell: Cell) -> Setup:
    conf, traffic = cell.config, cell.traffic
    ref = reference(conf)
    settings = conf["train"]
    cfg = program.model_config(cell.config_name, ref.program_kwargs(conf), settings)
    mesh = program.mesh(traffic["mesh"]["shape"], traffic["mesh"]["axes"])
    B, S = int(traffic["batch"]), int(traffic["seq"])
    example = {"tokens": np.zeros((B, S), np.int32), "targets": np.zeros((B, S), np.int32)}
    zoo, arts, ocfg, init_opt = program.train(
        cfg, settings["optimizer"], mesh, example, traffic["dp_mode"], traffic["schedule"])
    layout = ref.layout(conf)
    if weights.shapes(layout) != program.param_shapes(zoo):
        raise ValueError("the reference's parameter layout is not the program's")
    return Setup(cell, ref, ref.dims(conf), zoo, arts, ocfg, init_opt, mesh, layout,
                 jnp.dtype(settings["param_dtype"]), B, S, int(traffic["first_steps"]))


def start(su: Setup, seed: int):
    params = weights.make(su.layout, seed, su.pdtype, su.arts.param_sharding)
    opt = su.init_opt(params)
    batches = data.train_batches(seed, int(su.cell.traffic["pool"]), su.batch, su.seq,
                                 su.m.vocab, su.arts.batch_sharding["tokens"])
    return params, opt, batches


def first_steps(su: Setup, seed: int, params, opt, batches):
    """Drive the timed step through the first steps; returns the state and
    the readings {"losses", "grad", "change"}."""
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
    clip = min(1.0, su.ocfg.grad_clip / max(gnorm, 1e-12))
    grad = [float(x) / (1 - su.ocfg.b1) / clip for x in mu]
    chg = [float(x) for x in change(params, weights.root_key(seed, weights.WEIGHTS))]
    return (params, opt, met), {"losses": [float(x) for x in losses], "grad": grad, "change": chg}


def window(su: Setup, state, batches, seconds: float):
    """Run the timed loop; returns (state, steps, window seconds, the
    seconds between successive steps' losses)."""
    params, opt, met = state
    step = su.arts.step_fn
    pool = len(batches)
    n, prev, done = 0, None, []
    with common.no_compiles(), jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                params, opt, met = step(params, opt, batches[(su.first + n) % pool])
            n += 1
            if prev is not None:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    prev["loss"].block_until_ready()
                done.append(time.perf_counter())
            prev = met
            est = float(np.median(np.diff([t0] + done))) if done else 0.0
            if time.perf_counter() - t0 + est >= seconds:
                break
        with jax.profiler.TraceAnnotation("bench.wait"):
            met["loss"].block_until_ready()
        t1 = time.perf_counter()
    return (params, opt, met), n, t1 - t0, np.diff([t0] + done + [t1]).tolist()


def reference_readings(su: Setup, seed: int, fault: Optional[str] = None, fp8: bool = False) -> Dict:
    """The reference's readings of the same first steps (``fault``/``fp8``
    put a broken program or the control in the program's place)."""
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
    del mu, nu, batches
    change = jax.jit(lambda p, key: ref.change_norms(conf, key, p, jnp.float32))
    chg = [float(x) for x in change(params, weights.root_key(seed, weights.WEIGHTS))]
    return {"losses": [float(x) for x in losses], "grad": grad, "change": chg}


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float) -> Dict:
    su = build(cell)
    params, opt, batches = start(su, seed)
    state, prog = first_steps(su, seed, params, opt, batches)
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t_start
    tracer = common.Tracer() if trace else None
    if tracer:
        tracer.start()
    state, steps, win_s, gaps = window(su, state, batches, seconds)
    devices = list(su.mesh.devices.flat)
    mem = common.memory_peak(devices)
    trace_path = tracer.stop() if tracer else None
    del state, params, opt, batches
    gc.collect()

    ref = reference_readings(su, seed)
    numbers = compare.train_numbers(prog, ref)
    out = {
        "attempted": steps, "failed": 0, "numbers": numbers, "memory_peak_bytes": mem,
        "end_to_end": {
            "train_tokens_per_s": steps * su.batch * su.seq / win_s,
            "setup_s": setup_s,
        },
        "info": {"steps": steps, "window_s": win_s, "slowest_steps_s": sorted(gaps)[-3:],
                 "program": prog, "reference": ref},
    }
    if trace_path:
        peak = common.peak_of(devices)
        out["trace"] = common.reduce_trace(trace_path, {
            "kind": "train", "chips": len(devices), "peak": peak, "steps": steps,
            "train_step_flops": counts.train_step_flops(su.m, su.batch, su.seq),
            "flash_cost": {k: counts.flash_call(k, su.m, su.batch // len(devices), su.seq)
                           for k in ("fwd", "fwd_lse", "bwd_dq", "bwd_dkv")},
        })
    return out

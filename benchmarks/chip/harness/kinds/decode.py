"""Decode cells: closed-loop greedy decoding after a filled cache.

Set-up makes the serving weights (the type the configuration serves in)
and a cache whose first ``filled`` positions hold seeded K/V, both on the
device, then runs ``warmup_steps`` steps of the serving step of
``make_serve_step``.  Each step feeds every sequence its last token,
picks the next greedily and copies it to the host, as a streaming server
does; the step's latency is dispatch to tokens on the host.  When the
cache is full its index returns to ``filled`` (a new lap: a new request on
the same context).

After the window the K/V that the window wrote for ``check_rows`` rows
drawn from the seed are read back, the program's state is freed, and the
plain reference is run over those rows' served tokens: ``logit_gap`` is
the widest gap by which a served token's reference logit lies below the
reference's best, and ``kv_gap`` the widest relative gap between a
position's written K or V and the reference's (``compare``).  Both cover
the first lap: the positions from ``filled`` on that no later lap wrote
over.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import compare, counts, data, program, weights
from ..manifest import Cell, reference
from . import common


class Setup(NamedTuple):
    cell: Cell
    ref: Any
    m: Any
    zoo: Any
    arts: Any
    mesh: Any
    layout: Any
    wdtype: Any
    batch: int
    capacity: int
    filled: int
    greedy: Any


def build(cell: Cell) -> Setup:
    conf, traffic = cell.config, cell.traffic
    ref = reference(conf)
    settings = conf["serve"]
    cfg = program.model_config(cell.config_name, ref.program_kwargs(conf), settings)
    mesh = program.mesh([1], ["data"])
    B, C = int(traffic["batch"]), int(traffic["capacity"])
    zoo, arts = program.serve(cfg, mesh, B, C)
    layout = ref.layout(conf)
    if weights.shapes(layout) != program.param_shapes(zoo):
        raise ValueError("the reference's parameter layout is not the program's")
    greedy = jax.jit(lambda logits: jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None])
    return Setup(cell, ref, ref.dims(conf), zoo, arts, mesh, layout,
                 jnp.dtype(settings["param_dtype"]), B, C, int(traffic["filled"]), greedy)


def start(su: Setup, seed: int):
    params = weights.make(su.layout, seed, su.wdtype, su.arts.param_sharding)
    cache = data.filled_cache(seed, su.m.layers, su.batch, su.capacity, su.filled,
                              su.m.kv_heads, su.m.head_dim, jnp.dtype(su.cell.config["serve"]["compute_dtype"]),
                              su.arts.cache_sharding)
    tok = data.first_tokens(seed, su.batch, su.m.vocab)
    return params, cache, tok


class Served(NamedTuple):
    tokens: List[np.ndarray]      # (B, 1) per step, the first is the seeded input
    latency: List[float]          # seconds per step
    contexts: List[int]           # filled positions before each step


def loop(su: Setup, params, cache, tok, served: Served, steps: int = 0, seconds: float = 0.0):
    """Decode ``steps`` steps, or until ``seconds`` have passed."""
    decode, greedy = su.arts.decode_fn, su.greedy
    index = su.filled + len(served.latency) % (su.capacity - su.filled)
    t0 = time.perf_counter()
    n = 0
    while (steps and n < steps) or (seconds and time.perf_counter() - t0 < seconds):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            logits, cache = decode(params, cache, {"tokens": tok})
            tok = greedy(logits)
        with jax.profiler.TraceAnnotation("bench.fetch"):
            host = np.asarray(tok)
        served.latency.append(time.perf_counter() - t)
        served.tokens.append(host)
        served.contexts.append(index)
        n += 1
        index += 1
        if index == su.capacity:
            cache = dict(cache, index=jax.device_put(np.int32(su.filled), su.arts.cache_sharding["index"]))
            index = su.filled
    return cache, tok, time.perf_counter() - t0


def program_tokens(served: Served, su: Setup) -> np.ndarray:
    """(B, n + 1) inputs of the first lap: the seeded token, then each
    served token."""
    lap = su.capacity - su.filled
    toks = np.concatenate(served.tokens, axis=1)
    return toks[:, : lap + 1]


def first_lap_written(su: Setup, steps: int) -> range:
    """Indices j of the first lap's inputs whose K/V still sit at
    position ``filled + j`` after ``steps`` steps (later laps write over
    the first positions again)."""
    lap = su.capacity - su.filled
    return range(max(0, steps - lap), min(steps, lap))


def written_kv(su: Setup, cache, rows: List[int], js: range) -> np.ndarray:
    """(2, L, len(rows), len(js), Hk, Dh) K and V that the program's cache
    holds at positions ``filled + js`` of the given rows, in f32."""
    lo, hi = su.filled + js.start, su.filled + js.stop
    idx = np.asarray(rows)
    return np.stack([np.asarray(cache[w][:, idx, lo:hi].astype(jnp.float32)) for w in ("k", "v")])


def check_rows(su: Setup, seed: int) -> List[int]:
    rng = np.random.default_rng(seed)
    return sorted(rng.choice(su.batch, size=int(su.cell.traffic["check_rows"]), replace=False).tolist())


def reference_logits(su: Setup, seed: int, rows: List[int], inputs: np.ndarray, fp8: bool = False):
    """Reference logits (len(rows), n, V) at each input position of the
    given rows (inputs (len(rows), n)), and the K/V (2, L, len(rows), n,
    Hk, Dh) that a cache holds for those inputs."""
    ref, conf = su.ref, su.cell.config
    n = inputs.shape[1]
    pad = (-n) % 64
    toks = jnp.asarray(np.pad(inputs, ((0, 0), (0, pad))))
    params = weights.make(su.layout, seed, su.wdtype)
    cdtype = jnp.dtype(conf["serve"]["compute_dtype"])

    @jax.jit
    def run(params, toks, key, rows):
        pk, pv = data.cache_prefix(key, su.m.layers, rows, su.filled,
                                   su.m.kv_heads, su.m.head_dim, cdtype)
        p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
        return ref.decode_logits(ref.FP8 if fp8 else ref.F32, conf, p32,
                                 pk.astype(jnp.float32), pv.astype(jnp.float32), toks, su.filled)

    key = weights.root_key(seed, weights.CACHE)
    logits, k, v = run(params, toks, key, jnp.asarray(rows, jnp.int32))
    return np.asarray(logits)[:, :n], np.stack([np.asarray(k)[:, :, :n], np.asarray(v)[:, :, :n]])


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap over rows: ref_logits (R, n, V), tokens (R, n) served."""
    return max(compare.logit_gap(ref_logits[r], tokens[r]) for r in range(tokens.shape[0]))


def numbers(ref, toks: np.ndarray, kv: np.ndarray, js: range) -> Dict[str, float]:
    """The numbers compared: ``ref`` = reference_logits(...) over the
    inputs toks[:, :-1], ``kv`` = written_kv(...) at ``js``."""
    logits, ref_kv = ref
    return {"logit_gap": gaps(logits, toks[:, 1:]),
            "kv_gap": compare.kv_gap(kv, ref_kv[:, :, :, js.start:js.stop])}


def faulty(su: Setup, fault: str) -> Setup:
    """``su`` with its step's cache write broken: ``"no_write"`` keeps the
    cache as it was (the index still moves on), ``"misplaced"`` puts the
    new K/V one position later."""
    step = su.arts.decode_fn

    def broken(params, cache, batch):
        logits, new = step(params, cache, batch)
        i = cache["index"]
        if fault == "no_write":
            return logits, dict(new, k=cache["k"], v=cache["v"])
        moved = {w: jax.lax.dynamic_update_slice_in_dim(
                     cache[w], jax.lax.dynamic_slice_in_dim(new[w], i, 1, axis=2), i + 1, axis=2)
                 for w in ("k", "v")}
        return logits, dict(new, **moved)

    if fault not in ("no_write", "misplaced"):
        raise ValueError(fault)
    return su._replace(arts=dataclasses.replace(su.arts, decode_fn=jax.jit(broken)))


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float) -> Dict:
    su = build(cell)
    params, cache, tok = start(su, seed)
    served = Served([np.asarray(tok)], [], [])
    cache, tok, _ = loop(su, params, cache, tok, served, steps=int(cell.traffic["warmup_steps"]))
    setup_s = time.perf_counter() - t_start
    warm = len(served.latency)
    tracer = common.Tracer() if trace else None
    if tracer:
        tracer.start()
    with common.no_compiles(), jax.profiler.TraceAnnotation("bench.window"):
        cache, tok, win_s = loop(su, params, cache, tok, served, seconds=seconds)
    devices = list(su.mesh.devices.flat)
    mem = common.memory_peak(devices)
    trace_path = tracer.stop() if tracer else None
    rows = check_rows(su, seed)
    js = first_lap_written(su, len(served.latency))
    kv = written_kv(su, cache, rows, js)
    del params, cache, tok
    gc.collect()

    steps = len(served.latency) - warm
    lat = np.asarray(served.latency[warm:])
    toks = program_tokens(served, su)[rows]
    out = {
        "attempted": steps * su.batch, "failed": 0, "memory_peak_bytes": mem,
        "numbers": numbers(reference_logits(su, seed, rows, toks[:, :-1]), toks, kv, js),
        "end_to_end": {
            "decode_tokens_per_s": steps * su.batch / win_s,
            "decode_step_p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "setup_s": setup_s,
        },
        "info": {"steps": steps, "window_s": win_s, "median_step_ms": float(np.median(lat)) * 1e3,
                 "slowest_steps_ms": (np.sort(lat)[-5:] * 1e3).tolist(),
                 "checked_rows": rows, "checked_tokens": int(toks.shape[1] - 1),
                 "checked_kv_positions": len(js)},
    }
    if trace_path:
        pk = common.peak_of(devices)
        roof = sum(counts.roofline_s(*_cost(su, c), pk)[0] for c in served.contexts[warm:])
        out["trace"] = common.reduce_trace(trace_path, {
            "kind": "decode", "chips": 1, "peak": pk, "steps": steps, "decode_roofline_s": roof})
    return out


def _cost(su: Setup, ctx: int):
    c = counts.decode_step(su.m, su.batch, ctx, weight_itemsize=su.wdtype.itemsize)
    return c["flops"], c["bytes"]

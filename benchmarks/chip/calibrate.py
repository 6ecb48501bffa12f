#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip at the cell's
own size, all in one process (the benchmark's own runs never do this):

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 3 [--steps 260] [--out chiprun_out/calibrate]

For every seed, the timed path's numbers against the reference (the
lower readings).  For the first ``--control-seeds`` seeds also:

* the control: the reference computed with float8 (e4m3) matrix
  products, put in the program's place;
* each fault the cell can have: training -- put in the program's place
  by the reference: half of the batch left out ("half"), the exchange
  between replicas left out ("no_exchange", cells on several chips), and
  a state left unchanged ("unchanged", which needs no run: its norms are
  zero); decode -- one served token altered where it is produced
  ("altered_token"), and planted in the program's step: its cache write
  left out ("no_write") or put one position later ("misplaced").

Decode seeds run ``--steps`` steps (a run's warm-up and window).  Each
reading is printed as one JSON line and all of them are written to
``<out>/<cell>.json``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path


_T0 = time.perf_counter()


def _emit(rows, row):
    row["at_s"] = round(time.perf_counter() - _T0, 1)
    rows.append(row)
    print(json.dumps(row), flush=True)


def train_readings(cell, seeds, ctl, rows):
    from harness import compare
    from harness.kinds import train

    su = train.build(cell)
    replicas = su.mesh.devices.size
    for i, seed in enumerate(seeds):
        params, opt, batches = train.start(su, seed)
        state, prog = train.first_steps(su, seed, params, opt, batches)
        del state, params, opt, batches
        gc.collect()
        ref = train.reference_readings(su, seed)
        _emit(rows, {"seed": seed, "what": "program", **compare.train_numbers(prog, ref)})
        if i >= ctl:
            continue
        _emit(rows, {"seed": seed, "what": "control_fp8",
                     **compare.train_numbers(train.reference_readings(su, seed, fp8=True), ref)})
        _emit(rows, {"seed": seed, "what": "half",
                     **compare.train_numbers(train.reference_readings(su, seed, fault="half"), ref)})
        if replicas > 1:
            _emit(rows, {"seed": seed, "what": "no_exchange",
                         **compare.train_numbers(train.reference_readings(su, seed, fault="no_exchange"), ref)})
        still = {"losses": ref["losses"],
                 "grad": [0.0] * len(ref["grad"]), "change": [0.0] * len(ref["change"])}
        _emit(rows, {"seed": seed, "what": "unchanged", **compare.train_numbers(still, ref)})


def decode_readings(cell, seeds, ctl, steps, rows):
    import numpy as np

    from harness.kinds import decode

    def served_run(su, seed):
        """The timed path over ``steps`` steps: (served tokens of the
        checked rows, their written K/V, the first lap's positions)."""
        params, cache, tok = decode.start(su, seed)
        served = decode.Served([np.asarray(tok)], [], [])
        cache, tok, _ = decode.loop(su, params, cache, tok, served, steps=steps)
        rsel = decode.check_rows(su, seed)
        js = decode.first_lap_written(su, len(served.latency))
        kv = decode.written_kv(su, cache, rsel, js)
        del params, cache, tok
        gc.collect()
        return rsel, decode.program_tokens(served, su)[rsel], kv, js

    su = decode.build(cell)
    broken = {fault: decode.faulty(su, fault) for fault in ("no_write", "misplaced")}
    for i, seed in enumerate(seeds):
        rsel, toks, kv, js = served_run(su, seed)
        ref = decode.reference_logits(su, seed, rsel, toks[:, :-1])
        _emit(rows, {"seed": seed, "what": "program", **decode.numbers(ref, toks, kv, js),
                     "tokens": int(toks.shape[1] - 1)})
        if i >= ctl:
            continue
        c_logits, c_kv = decode.reference_logits(su, seed, rsel, toks[:, :-1], fp8=True)
        ctl_toks = np.concatenate([toks[:, :1], c_logits.argmax(-1)], axis=1)
        _emit(rows, {"seed": seed, "what": "control_fp8",
                     **decode.numbers(ref, ctl_toks, c_kv[:, :, :, js.start:js.stop], js)})
        bad = toks.copy()
        pos = 1 + int(np.random.default_rng(seed).integers(bad.shape[1] - 1))
        bad[0, pos] = (bad[0, pos] + 1 + int(np.random.default_rng(seed + 1).integers(su.m.vocab - 1))) % su.m.vocab
        _emit(rows, {"seed": seed, "what": "altered_token", **decode.numbers(ref, bad, kv, js)})
        for fault, fsu in broken.items():
            rsel, ftoks, fkv, fjs = served_run(fsu, seed)
            fref = decode.reference_logits(su, seed, rsel, ftoks[:, :-1])
            _emit(rows, {"seed": seed, "what": fault, **decode.numbers(fref, ftoks, fkv, fjs)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--steps", type=int, default=260, help="decode steps per seed")
    ap.add_argument("--out", default="chiprun_out/calibrate")
    args = ap.parse_args(argv)
    bench = Path(__file__).resolve().parent
    root = bench.parents[1]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.makedirs(root / ".jax_cache", exist_ok=True)   # JAX does not create it
    sys.path.insert(0, str(bench))
    import jax

    from run import _cache_settings

    _cache_settings(jax)
    from harness import manifest, program

    cell = manifest.cell(manifest.load(root), args.workload)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 3
    program.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    if cell.traffic["kind"] == "train":
        train_readings(cell, seeds, args.control_seeds, rows)
    else:
        decode_readings(cell, seeds, args.control_seeds, args.steps, rows)
    os.makedirs(args.out, exist_ok=True)
    Path(args.out, f"{cell.name}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

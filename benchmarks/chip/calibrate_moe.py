#!/usr/bin/env python3
"""``calibrate.py`` for cells of kind ``train_moe``, whose ``correct`` takes
one number more (``grad_error``, see ``harness/kinds/train_moe.py``):

    python3 benchmarks/chip/calibrate_moe.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 6 [--out chiprun_out/calibrate]

For every seed the timed path's numbers against the reference; for the
first ``--control-seeds`` seeds also the float8 control's, half of the
batch left out, and a state left unchanged.  Each reading is printed as
one JSON line and all of them are written to ``<out>/<cell>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=6)
    ap.add_argument("--out", default="chiprun_out/calibrate")
    args = ap.parse_args(argv)
    bench = Path(__file__).resolve().parent
    root = bench.parents[1]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.makedirs(root / ".jax_cache", exist_ok=True)   # JAX does not create it
    sys.path.insert(0, str(bench))
    import jax

    from calibrate import _emit
    from run import _cache_settings

    _cache_settings(jax)
    from harness import manifest, program
    from harness.kinds import train_moe

    cell = manifest.cell(manifest.load(root), args.workload)
    if cell.traffic["kind"] != "train_moe":
        print(f"calibrate_moe: {cell.name} is of kind {cell.traffic['kind']}", file=sys.stderr)
        return 2
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print(f"calibrate_moe: {cell.name} needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 3
    program.enable_compile_cache()
    rows = []
    train_moe.calibrate(cell, [int(s) for s in args.seeds.split(",")], args.control_seeds,
                        lambda row: _emit(rows, row))
    os.makedirs(args.out, exist_ok=True)
    Path(args.out, f"{cell.name}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, limits and per-layer metric readers are files of their own
under this directory (``harness/manifest.py`` says where).  Set-up (JAX's
start, weights and inputs made on the device from ``--seed``, compilation
or loading from the compilation cache at ``<checkout>/.jax_cache``, and the
cell's first steps) is timed as ``setup_s``; then the cell runs for
``--seconds``; then what the timed path produced is compared with a plain
reference.  ``--trace 1`` records the window with the JAX profiler and
reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
and their limits are also the last lines of standard error.  With no TPU,
or fewer chips than the cell asks for, the run prints no result and exits
with code 3.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path


def _err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = Path(__file__).resolve().parent
    root = bench.parents[1]
    # the program keeps its cache where this variable says; the path is
    # part of every entry's key, so it is fixed inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.makedirs(root / ".jax_cache", exist_ok=True)   # JAX does not create it
    sys.path.insert(0, str(bench))
    from harness import manifest

    cell = manifest.cell(manifest.load(root), args.workload)

    import jax

    _cache_settings(jax)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        _err(f"run: cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
             f"{len(devs)} {devs[0].platform} device(s)")
        return 3

    from harness import program

    program.enable_compile_cache()

    res = manifest.kind(cell.traffic).run(cell, args.seed, args.seconds, bool(args.trace), t_start)
    line, notes = report(cell, res, bool(args.trace), devs)
    for note in notes:
        _err(note)
    print(json.dumps(line), flush=True)
    return 0


def _cache_settings(jax) -> None:
    """Before anything compiles: keep every program, however quick to
    compile, and never evict (eviction reads per-entry access times that an
    entry written without eviction lacks, and then no entry is written)."""
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def report(cell, res, trace: bool, devs):
    """The result line of a run and the lines for standard error, the
    numbers compared and their limits last."""
    from harness import compare, manifest

    correct, rows = compare.judge(res["numbers"], cell.limits)
    notes = [f"{key}: {json.dumps(value)}" for key, value in res.get("info", {}).items()]
    metrics = {}
    if trace:
        ctx = res["trace"]
        for m in cell.per_layer:
            value = manifest.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": res["end_to_end"][m["name"]], "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    if trace:
        red = res["trace"]["reduced"]
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        line["breakdown"] = {"device_ops": [list(x) for x in red.device_ops],
                             "idle_gaps": [list(x) for x in red.idle_gaps]}
    line["compared"] = {name: {"value": value, "limit": limit} for name, value, limit in rows}
    notes += [f"compared {name} {value!r} limit {limit!r}" for name, value, limit in rows]
    notes.append(f"correct {correct}")
    return line, notes


if __name__ == "__main__":
    sys.exit(main())

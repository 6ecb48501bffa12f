#!/usr/bin/env python3
"""Device time of a training cell's step by the program's named scopes.

    python3 benchmarks/chip/scope_time.py --workload <cell> --seed <n> [--seconds 15] [--dump <file>]

Makes one traced run of the cell through ``run.py`` (its result line,
``correct`` included, is printed as ``run.py`` prints it), keeping the
window's events; then builds the cell's step again, loads its compiled
HLO from the compilation cache, maps each device op of the trace to its
``op_name`` and sums the ops' own time by scope (``harness/scopes.py``).
The last line of standard output is one JSON object: seconds by scope
over all cores, each scope's share of the busy time of all cores (the
denominator of ``flash_time_pct.train``), and how long building the map
took.  ``--dump`` writes the window's events with the ``op_name`` of each
op, for a test's recorded trace.  Needs a TPU: without one ``run.py``
exits with code 3, and so does this.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import tempfile
import time
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--dump")
    args = ap.parse_args(argv)

    bench = Path(__file__).resolve().parent
    sys.path.insert(0, str(bench))
    import run as runner
    from harness import manifest

    cell = manifest.cell(manifest.load(bench.parents[1]), args.workload)
    if cell.traffic["kind"] != "train":
        raise SystemExit(f"scope_time: cell {cell.name} is not a training cell")
    events = tempfile.NamedTemporaryFile(prefix="scope_time_", suffix=".json", delete=False).name
    os.environ["BENCH_TRACE_DUMP"] = events
    try:
        rc = runner.main(["--workload", args.workload, "--seed", str(args.seed),
                          "--seconds", str(args.seconds), "--trace", "1"])
        if rc:
            return rc
        from harness import xplane
        trace = xplane.read(events)
    finally:
        os.remove(events)

    import jax

    from harness import reduce, scopes
    from harness.kinds import train

    t0 = time.perf_counter()
    su = train.build(cell)
    params, opt, batches = train.start(su, args.seed)
    hlo = su.arts.step_fn.lower(params, opt, batches[0]).compile().as_text()
    names = scopes.op_names(hlo)
    map_s = time.perf_counter() - t0

    red = reduce.reduce(trace)
    scope_s = scopes.device_time(trace, names)
    busy = red.busy_s * red.cores
    share = scopes.shares(scope_s, busy)
    out = {
        "workload": cell.name, "seed": args.seed, "window_s": red.window_s,
        "busy_s": red.busy_s, "cores": red.cores, "scope_time_s": scope_s,
        "scope_share_pct": share, "scope_share_sum_pct": sum(share.values()),
        "flash_time_pct": 100.0 * sum(s for _, s in red.flash.values()) / busy,
        "map_build_s": map_s,
        "device": {"kind": jax.devices()[0].device_kind, "count": len(jax.devices())},
    }
    if args.dump:
        with gzip.open(args.dump, "wt") as f:
            json.dump({"trace": {"devices": {str(k): [list(e) for e in v] for k, v in trace.devices.items()},
                                 "host": [list(e) for e in trace.host]},
                       "op_names": names}, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract:
``us_per_call`` is the wall time of computing the benchmark quantity,
``derived`` the headline figure it reproduces.

  bench_table2        topology scalability/diameter/bisection   (Table 2)
  bench_table6        network cost model                        (Tables 3/6)
  bench_fig14a        all-to-all throughput by topology         (Fig. 14a)
  bench_fig14b        intra-mesh bandwidth sweep                (Fig. 14b)
  bench_fig15         All-Reduce algorithms across scales       (Fig. 15)
  bench_fig16         DP/CP bandwidth allocation                (Fig. 16)
  bench_fig17         availability under failures               (Fig. 17)
  bench_collectives   executable schedules: HLO collective bytes (Eq. 8)
  bench_kernels       Pallas kernels vs oracles (interpret mode)
  bench_dryrun        roofline table from results/dryrun

``--trace out.json`` records the whole harness as a Chrome trace-event
JSON (open in https://ui.perfetto.dev): every instrumented layer the
benchmarks exercise — flow solves, goodput estimates, OCS synthesis —
emits its spans into one timeline.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")


def bench_table2() -> None:
    from repro.core.topology import RailXConfig, table2_metrics

    t0 = time.perf_counter()
    cfg = RailXConfig(m=4, n=4, R=128)
    t = table2_metrics(cfg)
    us = (time.perf_counter() - t0) * 1e6
    for name, row in t.items():
        _row(
            f"table2_{name}", us / 3,
            f"scale={row['scale']:.0f};diam={row['diameter_ho']};bisect={row['bisection_per_chip']:.3f}",
        )


def bench_table6() -> None:
    from repro.core.cost import table3

    t0 = time.perf_counter()
    rows = table3()
    us = (time.perf_counter() - t0) * 1e6
    for r in rows:
        _row(
            f"table6_{r['name'].replace(' ', '_').replace('(', '').replace(')', '')}",
            us / len(rows),
            f"cost={r['cost_musd']}M;perInject={r['cost_per_inject_x']}x;perGBW={r['cost_per_gbw_x']}x",
        )


def bench_fig14a() -> None:
    """All-to-all throughput at scale 16 (1,024 chips), one curve per
    architecture in the ``repro.arch`` registry declaring a Fig. 14
    entry point — registering a new fabric adds its curve here for free.
    The vectorized engine routes each full demand matrix in well under a
    second (see BENCH_simulator.json for the trajectory up to 4,096
    chips exact / 102,400 chips via symmetry)."""
    from repro.arch import fig14_archs
    from repro.core.simulator import alltoall_throughput

    m, scale, inj = 2, 16, 8.0
    archs = fig14_archs()
    # warm up the vectorized engine (numpy/scipy imports) off the clock
    warm = archs[0].flow_fig14(2, m, 2.0, inj)
    alltoall_throughput(warm.net, warm.chips, inj)
    for arch in archs:
        fb = arch.flow_fig14(scale, m, 2.0, inj)
        t0 = time.perf_counter()
        thr = alltoall_throughput(fb.net, fb.chips, inj)
        us = (time.perf_counter() - t0) * 1e6
        _row(
            f"fig14a_{arch.fig14_label}", us,
            f"a2a_flits_per_cycle_chip={thr:.3f}",
        )


def bench_fig14b() -> None:
    from repro.arch import get
    from repro.core.simulator import alltoall_throughput

    m, scale, inj = 2, 16, 4.0
    railx = get("railx-hyperx")
    for k in (1.0, 2.0, 4.0, 8.0):
        fb = railx.flow_fig14(scale, m, k, inj)
        t0 = time.perf_counter()
        thr = alltoall_throughput(fb.net, fb.chips, inj)
        us = (time.perf_counter() - t0) * 1e6
        _row(f"fig14b_k{int(k)}", us, f"a2a={thr:.3f}")


def bench_fig15() -> None:
    """All-Reduce curves: the per-fabric closed forms are resolved via
    the ``repro.arch`` registry inside ``paper_fig15_curves``."""
    from repro.core.analytical import paper_fig15_curves

    t0 = time.perf_counter()
    curves = paper_fig15_curves(
        [2 ** 20, 2 ** 30], [8, 32, 128], m=2, n=2
    )
    us = (time.perf_counter() - t0) * 1e6
    for alg, by_p in curves.items():
        for p, by_v in by_p.items():
            for v, t in by_v.items():
                _row(
                    f"fig15_{alg}_p{p}_V{int(v//2**20)}MiB",
                    us / 18,
                    f"allreduce_s={t:.6f}",
                )


def bench_fig16() -> None:
    from repro.core.mapping import allocate_bandwidth_static

    for seq, (v_dp, v_cp) in {
        "8k": (4e9, 0.5e9),
        "32k": (4e9, 2e9),
        "128k": (4e9, 8e9),
    }.items():
        t0 = time.perf_counter()
        n_dp, n_cp, t = allocate_bandwidth_static(v_dp, v_cp, 10, 50e9)
        n_dp2, n_cp2, t2 = allocate_bandwidth_static(
            v_dp, v_cp, 10, 50e9, overlap1=0.02
        )
        us = (time.perf_counter() - t0) * 1e6
        _row(
            f"fig16_seq{seq}", us,
            f"dp:cp={n_dp}:{n_cp};with_overlap={n_dp2}:{n_cp2}",
        )


def bench_fig17() -> None:
    from repro.core.availability import availability_curve

    t0 = time.perf_counter()
    curve = availability_curve(32, [0.0005, 0.001, 0.005, 0.01], samples=30)
    us = (time.perf_counter() - t0) * 1e6
    for rate, avail in curve.items():
        _row(f"fig17_rate{rate}", us / 4, f"availability={avail:.4f}")


def bench_collectives() -> None:
    """Eq. 8 executable check: inter-axis AR bytes, flat vs hierarchical,
    from compiled HLO on a 16-device two-level mesh (subprocess)."""
    import subprocess
    import textwrap

    code = """
import jax, jax.numpy as jnp, re, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.collectives import make_all_reduce_fn
mesh = jax.make_mesh((4, 4), ("node", "mesh"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
sds = jax.ShapeDtypeStruct((256, 256), jnp.float32,
        sharding=NamedSharding(mesh, P("node", None)))
out = {}
for sched in ("flat", "hierarchical", "ring2d"):
    fn = make_all_reduce_fn(mesh, P("node", None), sched,
                            intra_axes="mesh", inter_axes="node")
    txt = fn.lower(sds).compile().as_text()
    total = 0
    for m in re.finditer(r"= \\S*?f32\\[([\\d,]*)\\][^\\n]*? all-reduce\\(", txt):
        n = 1
        for d in m.group(1).split(","):
            if d: n *= int(d)
        total += n * 4
    out[sched] = total
print(json.dumps(out))
"""
    env = dict(os.environ)
    # 16 virtual host devices: the child must not reach for the chip, which
    # this process or another may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    us = (time.perf_counter() - t0) * 1e6
    if out.returncode != 0:
        raise RuntimeError(
            f"collectives child exited {out.returncode}:\n{out.stderr[-4000:]}"
        )
    data = json.loads(out.stdout.strip().splitlines()[-1])
    ratio = data["flat"] / max(data["hierarchical"], 1)
    _row(
        "collectives_eq8", us,
        f"AR_bytes flat={data['flat']} hier={data['hierarchical']} saving={ratio:.1f}x",
    )


def bench_kernels() -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.mlstm.ops import mlstm
    from repro.kernels.mlstm.ref import mlstm_ref
    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_ref

    rng = np.random.RandomState(0)
    q = jnp.array(rng.randn(1, 4, 256, 64), jnp.float32)
    k = jnp.array(rng.randn(1, 2, 256, 64), jnp.float32)
    v = jnp.array(rng.randn(1, 2, 256, 64), jnp.float32)
    t0 = time.perf_counter()
    out = flash_attention_fwd(q, k, v, causal=True, interpret=True)
    us = (time.perf_counter() - t0) * 1e6
    err = float(jnp.abs(out - attention_ref(q, k, v, causal=True)).max())
    _row("kernel_flash_attention", us, f"max_err={err:.2e}")

    x = jnp.array(rng.randn(1, 128, 2, 32), jnp.float32)
    dt = jnp.array(np.abs(rng.randn(1, 128, 2)) * 0.1 + 0.01, jnp.float32)
    Bm = jnp.array(rng.randn(1, 128, 16), jnp.float32)
    Cm = jnp.array(rng.randn(1, 128, 16), jnp.float32)
    A = -jnp.ones((2,), jnp.float32)
    t0 = time.perf_counter()
    out = ssd(x, dt, Bm, Cm, A, chunk=32)
    us = (time.perf_counter() - t0) * 1e6
    ref = ssd_ref(x, dt, Bm, Cm, A)
    err = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
    _row("kernel_ssd", us, f"rel_err={err:.2e}")

    qm = jnp.array(rng.randn(1, 128, 2, 32) / np.sqrt(32), jnp.float32)
    km = jnp.array(rng.randn(1, 128, 2, 32), jnp.float32)
    vm = jnp.array(rng.randn(1, 128, 2, 32), jnp.float32)
    ig = jnp.array(rng.randn(1, 128, 2), jnp.float32)
    import jax

    lf = jnp.array(jax.nn.log_sigmoid(jnp.array(rng.randn(1, 128, 2) + 2)))
    t0 = time.perf_counter()
    out = mlstm(qm, km, vm, ig, lf, chunk=32)
    us = (time.perf_counter() - t0) * 1e6
    ref = mlstm_ref(qm, km, vm, ig, lf)
    err = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
    _row("kernel_mlstm", us, f"rel_err={err:.2e}")


def bench_dryrun() -> None:
    """Roofline summary from the dry-run artifacts (no recompute)."""
    import glob

    t0 = time.perf_counter()
    files = sorted(glob.glob(os.path.join(RESULTS, "dryrun", "*__pod1.json")))
    us = (time.perf_counter() - t0) * 1e6
    n_ok = 0
    for f in files:
        d = json.load(open(f))
        if d["status"] != "OK":
            continue
        n_ok += 1
        r = d["report"]
        _row(
            f"dryrun_{d['cell']}", us / max(len(files), 1),
            f"dom={r['dominant']};frac={r['roofline_fraction']:.4f};"
            f"coll_bytes={r['collective_bytes_per_dev']:.3e}",
        )
    if not n_ok:
        _row("dryrun", us, "no_results__run_launch.dryrun_first")


def _run_all() -> None:
    print("name,us_per_call,derived")
    bench_table2()
    bench_table6()
    bench_fig14a()
    bench_fig14b()
    bench_fig15()
    bench_fig16()
    bench_fig17()
    bench_collectives()
    bench_kernels()
    bench_dryrun()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--trace", metavar="OUT.json", default=None,
        help="record a Chrome trace-event JSON of the whole harness "
             "(open in https://ui.perfetto.dev)",
    )
    args = ap.parse_args()

    if args.trace:
        from repro.obs import Tracer, tracing

        tracer = Tracer(process="bench-run")
        with tracing(tracer):
            _run_all()
        tracer.write(args.trace)
        print(f"wrote trace {args.trace}")
    else:
        _run_all()


if __name__ == "__main__":
    main()

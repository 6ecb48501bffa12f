"""The trace reduction: busy union, idle share, exposed collective time,
flash kernel sums and the breakdown, on a hand-made trace with known
answers and on a trace recorded on one TPU v5e chip."""

import gzip
import json
import sys
import tempfile
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import reduce as R  # noqa: E402
from harness import xplane  # noqa: E402
from harness.xplane import Event, Trace  # noqa: E402

BF = "{3,2,1,0:T(8,128)(2,1)}"
FWD_LSE = (f"%closed_call.11 = (bf16[2,32,4096,128]{BF}, f32[2,32,4096,1]{{3,2,1,0:T(8,128)}}) "
           f"custom-call(bf16[2,32,4096,128]{BF} %a, bf16[2,32,4096,128]{BF} %b, "
           f"bf16[2,32,4096,128]{BF} %c), custom_call_target=\"tpu_custom_call\"")
DQ = (f"%checkpoint.21 = bf16[2,32,4096,128]{BF} custom-call(bf16[2,32,4096,128]{BF} %a, "
      f"bf16[2,32,4096,128]{BF} %b, bf16[2,32,4096,128]{BF} %c, bf16[2,32,4096,128]{BF} %d, "
      f"f32[2,32,4096,1]{{3,2,1,0}} %e, f32[2,32,4096,1]{{3,2,1,0}} %f), custom_call_target=\"tpu_custom_call\"")
DKV = (f"%checkpoint.20 = (bf16[2,32,4096,128]{BF}, bf16[2,32,4096,128]{BF}) custom-call(bf16[2,32,4096,128]{BF} %a, "
       f"bf16[2,32,4096,128]{BF} %b, bf16[2,32,4096,128]{BF} %c, bf16[2,32,4096,128]{BF} %d, "
       f"f32[2,32,4096,1]{{3,2,1,0}} %e, f32[2,32,4096,1]{{3,2,1,0}} %f), custom_call_target=\"tpu_custom_call\"")
FUSION = "%fusion.12 = bf16[2,4096,4096]{1,2,0:T(8,128)(2,1)} fusion(bf16[2,4096,4096]{1,2,0} %x), kind=kLoop"
WHILE = "%while.9 = (s32[]{:T(128)}, bf16[2,4096,4096]{1,2,0:T(8,128)(2,1)}) while((s32[], bf16[2,4096,4096]) %t)"
ALL_REDUCE = "%all-reduce.3 = f32[4096,128]{1,0:T(8,128)} all-reduce(f32[4096,128]{1,0} %g), replica_groups={{0,1}}"
ALL_GATHER = ("%all-gather-start.1 = (f32[1024]{0}, f32[2048]{0}) all-gather-start(f32[1024]{0} %p), "
              "replica_groups={{0,1}}, dimensions={0}")

US = 1000.0   # the trace's unit is the nanosecond


def hand_made():
    """Core 0: a while loop [0, 80] holding a fusion [0, 30], the flash
    forward [30, 60] and an all-reduce [60, 80]; then a fusion [90, 100];
    an async all-gather [50, 95] on the async line.  Core 1: one fusion
    [0, 60].  Window [0, 120]; the host waits from 78 on."""
    d0 = [Event(WHILE, 0, 80 * US, "XLA Ops"), Event(FUSION, 0, 30 * US, "XLA Ops"),
          Event(FWD_LSE, 30 * US, 60 * US, "XLA Ops"), Event(ALL_REDUCE, 60 * US, 80 * US, "XLA Ops"),
          Event(FUSION.replace(".12", ".13"), 90 * US, 100 * US, "XLA Ops"),
          Event(ALL_GATHER, 50 * US, 95 * US, "Async XLA Ops")]
    d1 = [Event(FUSION, 0, 60 * US, "XLA Ops")]
    host = [Event("bench.window", 0, 120 * US, "python3"), Event("bench.dispatch", 1 * US, 2 * US, "python3"),
            Event("bench.wait", 78 * US, 120 * US, "python3")]
    return Trace({0: sorted(d0, key=lambda e: (e.start, -e.end)), 1: d1}, host)


def test_interval_arithmetic():
    assert R.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert R.total(R.union([(0, 2), (1, 3)])) == 3
    assert R.minus([(0, 10)], [(2, 3), (5, 6), (9, 12)]) == 7
    assert R.minus([(0, 4), (6, 10)], [(3, 7)]) == 6
    assert R.clip([(0, 5), (6, 9)], 1, 7) == [(1, 5), (6, 7)]


def test_kernel_and_collective_recognition():
    assert R.flash_kind(FWD_LSE) == "fwd_lse"
    assert R.flash_kind(DQ) == "bwd_dq"
    assert R.flash_kind(DKV) == "bwd_dkv"
    assert R.flash_kind(FUSION) is None and R.flash_kind(WHILE) is None
    assert R.opcode(ALL_REDUCE) == "all-reduce" and R.is_collective(ALL_REDUCE)
    assert R.is_collective(ALL_GATHER) and not R.is_collective(FWD_LSE)
    assert R.group_name(FWD_LSE) == "flash.fwd_lse" and R.group_name(FUSION) == "fusion"


def test_reduction_of_a_hand_made_trace():
    r = R.reduce(hand_made())
    assert r.window_s == pytest.approx(120e-6)
    assert r.cores == 2
    # busy: core 0 [0, 80] + [90, 100] = 90, core 1 60; mean 75 of 120
    assert r.busy_s == pytest.approx(75e-6)
    assert r.idle_share == pytest.approx(1 - 75 / 120)
    assert r.flash == {"fwd_lse": (1, pytest.approx(30e-6))}
    # collectives on core 0: [50, 95]; compute leaves [0, 60] and [90, 100]
    # leave [60, 90] exposed; core 1 has none: means over two cores
    assert r.collective_s == pytest.approx(45e-6 / 2)
    assert r.collective_exposed_s == pytest.approx(30e-6 / 2)
    ops = dict(r.device_ops)
    assert ops["fusion"] == pytest.approx(100e-6) and ops["flash.fwd_lse"] == pytest.approx(30e-6)
    assert "while" not in ops
    # idle gaps: core 1 [60, 120], core 0 [100, 120] and [80, 90], all while the host waits
    assert r.idle_gaps == [("bench.wait", pytest.approx(60e-6)), ("bench.wait", pytest.approx(20e-6)),
                           ("bench.wait", pytest.approx(10e-6))]


def test_events_round_trip_through_json():
    t = hand_made()
    with tempfile.TemporaryDirectory() as d:
        xplane.dump(t, f"{d}/t.json")
        assert xplane.read(f"{d}/t.json") == t


def test_recorded_trace_of_one_chip():
    """Three steps of qwen3-8b.train-4k on one TPU v5e, recorded by
    ``run.py --trace 1`` with ``BENCH_TRACE_DUMP`` and cut to the window's
    first steps (names of ops other than kernels shortened)."""
    with gzip.open(BENCH / "tests" / "data" / "qwen3-8b.train-4k.trace.json.gz", "rt") as f:
        raw = json.load(f)
    t = Trace({int(k): [Event(*e) for e in v] for k, v in raw["trace"]["devices"].items()},
              [Event(*e) for e in raw["trace"]["host"]])
    r = R.reduce(t)
    want = raw["expect"]
    assert r.cores == 1 and r.collective_s == 0
    assert {k: n for k, (n, _) in r.flash.items()} == want["flash_calls"]
    assert r.window_s == pytest.approx(want["window_s"])
    # the expectation is a 1-us timeline painted event by event
    assert r.busy_s == pytest.approx(want["busy_s"], rel=2e-3)
    assert r.busy_s <= r.window_s
    assert sum(s for _, s in r.flash.values()) == pytest.approx(want["flash_s"])


def test_a_cpu_profile_has_host_spans_and_no_device():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = f(x)
            y.block_until_ready()
        jax.profiler.stop_trace()
        t = xplane.load(xplane.find(d))
    assert [e.name for e in t.host] == ["bench.window", "bench.dispatch"]
    assert t.devices == {}
    with pytest.raises(ValueError):
        R.reduce(t)

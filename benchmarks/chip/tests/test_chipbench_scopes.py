"""Device time by the program's named scopes: the innermost-scope rule on
``op_name`` paths as JAX writes them, kernel names, the map from an HLO
module's text, and the sums by scope, on hand-made input and on a trace
recorded on one TPU v5e chip."""

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import scopes as SC  # noqa: E402

STEP = "jit(step)"
FWD = f"{STEP}/jvp(layers)/while/body/closed_call"
BWD = f"{STEP}/transpose(jvp(layers))/while/body/closed_call/checkpoint"


@pytest.mark.parametrize("op_name,scope", [
    (f"{FWD}/attention/dot_general", "attention"),
    (f"{BWD}/transpose(jvp(attention))/dot_general", "attention"),
    (f"{BWD}/rematted_computation/mlp/mul", "mlp"),
    (f"{BWD}/rematted_computation/attention/flash_fwd_lse/pallas_call", "attention"),
    (f"{STEP}/transpose(jvp(layers))/while/body/dynamic_update_slice", "layers"),
    (f"{STEP}/jvp(embed)/jit(_take)/gather", "embed"),
    (f"{STEP}/transpose(jvp(head))/reduce_sum", "head"),
    (f"{STEP}/optimizer/sqrt", "optimizer"),
    (f"{STEP}/shard_map/grad_reduce/psum_scatter", "grad_reduce"),
    (f"{STEP}/layers/while/body/attention/kv_cache/dynamic_update_slice", "kv_cache"),
    (f"{STEP}/jvp(layers)/while/body/moe/all_to_all", "moe"),
    (f"{STEP}/shard_map/psum", "unscoped"),
    ("", "unscoped"),
    # a scope's name inside another word is not the scope
    (f"{STEP}/attention_probs/dot_general", "unscoped"),
])
def test_innermost_scope(op_name, scope):
    assert SC.scope_of(op_name) == scope


def test_kernel_names():
    assert SC.kernel_of(f"{BWD}/attention/flash_bwd_dkv/pallas_call") == "flash_bwd_dkv"
    assert SC.kernel_of(f"{FWD}/attention/dot_general") is None
    assert SC.kernel_of("pallas_call") is None


HLO = """HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_computation.1 (param_0: bf16[8,16]) -> bf16[8,16] {
  %param_0 = bf16[8,16]{1,0} parameter(0)
  ROOT %convolution.3 = bf16[8,16]{1,0} convolution(%param_0, %param_0), dim_labels=bf_io->bf, metadata={op_name="jit(step)/jvp(layers)/while/body/mlp/dot_general" stack_frame_id=3}
}

ENTRY %main (p: bf16[8,16]) -> bf16[8,16] {
  %p = bf16[8,16]{1,0} parameter(0), metadata={op_name="p"}
  %copy.2 = bf16[8,16]{0,1} copy(%p)
  %flash_fwd_lse.1 = (bf16[8,16]{1,0}) custom-call(%copy.2), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step)/attention/flash_fwd_lse/pallas_call" stack_frame_id=2}
  ROOT %fusion.7 = bf16[8,16]{1,0} fusion(%p), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(layers)/while/body/mlp/dot_general" stack_frame_id=3}
}
"""


def test_op_names_of_an_hlo_module():
    names = SC.op_names(HLO)
    assert names["convolution.3"].endswith("/mlp/dot_general")
    assert names["fusion.7"].endswith("/mlp/dot_general")
    assert names["flash_fwd_lse.1"] == "jit(step)/attention/flash_fwd_lse/pallas_call"
    assert names["p"] == "p"
    assert "copy.2" not in names          # no metadata: unscoped


def test_sums_by_scope():
    got = SC.totals([(f"{FWD}/attention/dot_general", 2.0), (f"{BWD}/mlp/mul", 1.0),
                     (f"{STEP}/transpose(jvp(layers))/while/body/add", 0.5), ("", 0.25),
                     (f"{BWD}/transpose(jvp(attention))/flash_bwd_dq/pallas_call", 1.0)])
    assert got["attention"] == 3.0 and got["mlp"] == 1.0 and got["layers"] == 0.5
    assert got["unscoped"] == 0.25
    assert set(got) == set(SC.SCOPES) | {"unscoped"}
    assert SC.shares(got, 4.75)["attention"] == pytest.approx(100 * 3 / 4.75)


def test_device_time_of_a_hand_made_trace():
    """Core 0: a loop [0, 80] whose body runs an MLP product [0, 30], the
    flash forward [30, 60] and a stacking op [70, 80]; after it an Adam
    update [90, 130] that the window [0, 120] cuts; an async copy on the
    other line.  Core 1: one op with no metadata [0, 50].  The loop's own
    time [60, 70] is the loop's scope's; the seconds sum to busy time."""
    from harness import reduce as R
    from harness.xplane import Event, Trace

    us = 1000.0
    names = {"while.1": f"{STEP}/jvp(layers)/while", "fusion.2": f"{FWD}/mlp/dot_general",
             "flash_fwd_lse.3": f"{FWD}/attention/flash_fwd_lse/pallas_call",
             "dynamic-update-slice.4": f"{STEP}/jvp(layers)/while/body/dynamic_update_slice",
             "fusion.5": f"{STEP}/optimizer/add"}

    def ev(name, a, b, line="XLA Ops"):
        return Event(f"%{name} = f32[8]{{0}} op()", a * us, b * us, line)

    d0 = [ev("while.1", 0, 80), ev("fusion.2", 0, 30), ev("flash_fwd_lse.3", 30, 60),
          ev("dynamic-update-slice.4", 70, 80), ev("fusion.5", 90, 130),
          ev("copy-start.6", 0, 100, "Async XLA Ops")]
    trace = Trace({0: d0, 1: [ev("copy.7", 0, 50)]}, [Event("bench.window", 0, 120 * us, "python3")])
    got = SC.device_time(trace, names)
    assert got["mlp"] == pytest.approx(30e-6) and got["attention"] == pytest.approx(30e-6)
    assert got["layers"] == pytest.approx(20e-6)        # the loop's own [60, 70] and the stacking op
    assert got["optimizer"] == pytest.approx(30e-6) and got["unscoped"] == pytest.approx(50e-6)
    red = R.reduce(trace)
    assert sum(got.values()) == pytest.approx(red.busy_s * red.cores)


def test_recorded_trace_with_scopes():
    """Four steps of qwen3-8b.train-4k on one TPU v5e with the program's
    scopes, recorded by ``scope_time.py --dump`` (the window's events and
    the ``op_name`` of each instruction in them; names of ops other than
    kernels shortened)."""
    from harness import reduce as R
    from harness.xplane import Event, Trace

    with gzip.open(BENCH / "tests" / "data" / "qwen3-8b.train-4k.scoped.trace.json.gz", "rt") as f:
        raw = json.load(f)
    trace = Trace({int(k): [Event(*e) for e in v] for k, v in raw["trace"]["devices"].items()},
                  [Event(*e) for e in raw["trace"]["host"]])
    names, want = raw["op_names"], raw["expect"]
    red = R.reduce(trace)
    assert {k: n for k, (n, _) in red.flash.items()} == want["flash_calls"]
    got = SC.device_time(trace, names)
    assert got == pytest.approx(want["scope_s"])
    share = SC.shares(got, red.busy_s * red.cores)
    assert sum(share.values()) == pytest.approx(100.0, abs=0.5)
    assert share[SC.UNSCOPED] < 5.0
    flash_pct = 100.0 * sum(s for _, s in red.flash.values()) / (red.busy_s * red.cores)
    assert share["attention"] >= flash_pct
    # every kernel call carries the name of the kernel its signature says
    for evs in trace.devices.values():
        for e in evs:
            kind = R.flash_kind(e.name)
            if kind:
                assert SC.kernel_of(names[R._split_instruction(e.name)[0]]) == f"flash_{kind}"

"""Cells at a size that a CPU test run can hold: the real cells' traffic
kinds, mixes and code paths, with the tiny dense configuration of
``data/tiny-dense.json`` (the widths of a toy model, the equations of the
benchmark's configurations) and short sequences.

The limits are this size's own, set as the cells' are: between the
program's readings over seeds (bf16 against the f32 reference) and the
float8 control's or a fault's.  Readings on the CPU over six seeds:
program loss_gap <= 9.3e-4, grad_gap <= 5.9e-4, change_gap <= 1.2e-3;
control loss_gap >= 3.4e-3 and grad_gap >= 4.6e-3 (one of the two is
over 4.6e-3 on every seed), change_gap 2.1e-3 to 3.9e-3 (under three
times the program's: its upper reading is the unchanged state's 1);
decode (2 rows, up to 256 tokens, lap 256) program logit_gap <= 1.9e-2
and kv_gap <= 8.4e-3 over eight seeds, control logit_gap >= 5.2e-2 and
kv_gap >= 7.6e-2 over six; a cache write left out reads kv_gap 1, one
put a position later about 1.9 (its logit_gap stays near the program's).
"""

import json
import sys
import time
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness.manifest import Cell  # noqa: E402

CONFIG = json.loads((BENCH / "tests" / "data" / "tiny-dense.json").read_text())
TRAIN_LIMITS = {"loss_gap": {"limit": 2.5e-3}, "grad_gap": {"limit": 2.5e-3}, "change_gap": {"limit": 1e-2}}
DECODE_LIMITS = {"logit_gap": {"limit": 2e-2}, "kv_gap": {"limit": 3e-2}}
CPU = types.SimpleNamespace(platform="cpu", device_kind="cpu")


def _end_to_end(like: str):
    """The end-to-end metrics the real cell ``like`` reports."""
    from harness import manifest

    return manifest.cell(manifest.load(), like).end_to_end


# what the decode cell reports (it has no entry in BENCHMARK.json until its
# limits are set on the chip)
DECODE_END_TO_END = [{"name": "decode_tokens_per_s", "unit": "tokens/s"},
                     {"name": "decode_step_p95_ms", "unit": "ms"},
                     {"name": "setup_s", "unit": "s"}]


def train_cell(mesh=((1,), ("data",)), dp_mode="gspmd_fsdp", batch=2) -> Cell:
    traffic = {"kind": "train", "batch": batch, "seq": 64,
               "mesh": {"shape": list(mesh[0]), "axes": list(mesh[1])},
               "dp_mode": dp_mode, "schedule": "hierarchical", "first_steps": 3, "pool": 4}
    return Cell("tiny.train", "tiny-dense", "tiny-train", 1, CONFIG, traffic, TRAIN_LIMITS,
                _end_to_end("qwen3-8b.train-4k"), [])


def decode_cell() -> Cell:
    traffic = {"kind": "decode", "batch": 4, "capacity": 448, "filled": 192,
               "warmup_steps": 2, "check_rows": 2}
    return Cell("tiny.decode", "tiny-dense", "tiny-decode", 1, CONFIG, traffic, DECODE_LIMITS,
                DECODE_END_TO_END, [])


def run(cell: Cell, seed: int, seconds: float = 0.5):
    """Everything a run does after the look for a chip: set-up, window,
    reference and the result line; returns that line."""
    import run as runner
    from harness import manifest

    res = manifest.kind(cell.traffic).run(cell, seed, seconds, False, time.perf_counter())
    line, _ = runner.report(cell, res, False, [CPU])
    return line

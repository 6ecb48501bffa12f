"""A data-parallel run (four virtual CPU devices, the rail-hierarchical
step) is correct, and comes out not correct when the exchange between
chips is left out."""

import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

CHILD = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import chipbench_tiny as tiny
cell = tiny.train_cell(mesh=((2, 2), ("pod", "data")), dp_mode="manual_hier", batch=4)
out = {"sound": tiny.run(cell, 2**31 + 3)}
import repro.train.train_step as ts
ts.tree_hierarchical_all_reduce = lambda grads, intra_axes, inter_axes: grads
out["no_exchange"] = tiny.run(cell, 2**31 + 3)
print(json.dumps({k: {"correct": v["correct"], "compared": v["compared"]} for k, v in out.items()}))
"""


def test_the_exchange_between_chips_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, str(HERE)], capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["sound"]["correct"], got
    assert not got["no_exchange"]["correct"], got

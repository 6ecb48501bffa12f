"""The command as the benchmark's contract runs it, where there is no TPU:
it exits non-zero and prints no result, in the repository and in a
directory that holds only BENCHMARK.json and the benchmark's files."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]


def _run(cwd, workload="qwen3-8b.train-4k", trace=0):
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    cmd = [sys.executable if w == "python3" else w for w in man["command"]]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd + ["--workload", workload, "--seed", str(2**31 + 5), "--seconds", "1",
                                 "--trace", str(trace)],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("trace", [0, 1])
def test_no_tpu_no_result(trace):
    p = _run(ROOT, trace=trace)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_only_the_benchmarks_files_is_not_enough(tmp_path):
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in man["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout

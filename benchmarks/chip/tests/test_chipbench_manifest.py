"""BENCHMARK.json against the benchmark's contract, and every file it names."""

import json
import math
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
# keys that name a width, which may never be cut
WIDTH = re.compile(r"(hidden_size|intermediate_size|latent|state_size|proj|_dim$|_rank$|expan|per_tok)")


@pytest.fixture(scope="module")
def man():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_sizes(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(man["paths"]) <= 16 and 1 <= len(man["command"]) <= 32
    for p in man["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    for word in man["command"]:
        assert TEXT.match(word) and not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in man["paths"]), word
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51


def test_a_full_check_of_24_cells_fits_its_time(man):
    runs = 2 + 14 * 24
    assert runs * (man["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_are_unique_and_well_formed(man, section):
    names = [x["name"] for x in man[section]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n


def test_configs(man):
    used = {w["config"] for w in man["workloads"]}
    files = set()
    assert 1 <= len(man["configs"]) <= 24
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert TEXT.match(c["source"]) and TEXT.match(c["why"])
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"]) and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert conf["published"][key] != conf[key]
        assert conf["departures"], "each configuration lists what its reference follows of the program"
        assert (BENCH / "references" / f"{conf['reference']}.py").exists()


def test_workloads(man):
    configs = {c["name"] for c in man["configs"]}
    pairs = set()
    four = 0
    assert 1 <= len(man["workloads"]) <= 24
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"]) and TEXT.match(w["why"])
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "harness" / "kinds" / f"{traffic['kind']}.py").exists()
        limits = json.loads((BENCH / "limits" / f"{w['name']}.json").read_text())
        for name, lim in limits.items():
            assert NAME.match(name)
            assert lim["lower"] < lim["limit"] < lim["upper"], name
    assert four <= max(1, len(man["workloads"]) // 2)


def _cells_of(man, metric):
    return metric.get("workloads", [w["name"] for w in man["workloads"]])


def test_metrics(man):
    cells = {w["name"] for w in man["workloads"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(_cells_of(man, m)) <= cells
    for cell in cells:
        reported = [m for m in man["end_to_end"] if cell in _cells_of(man, m)]
        assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
        assert any(cell in _cells_of(man, m) for m in man["per_layer"])
    layers = {}
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") and TEXT.match(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in _cells_of(man, m):
            assert cell in _cells_of(man, e2e[m["moves"]]), (m["name"], cell)
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_every_model_cell_reports_a_step_mfu(man):
    for w in man["workloads"]:
        assert any("mfu" in m["name"] and w["name"] in _cells_of(man, m) for m in man["per_layer"])


def test_the_references_layout_is_the_programs():
    from harness import manifest, program, weights

    man = manifest.load()
    for c in man["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        ref = manifest.reference(conf)
        settings = conf["train"] if "train" in conf else conf["serve"]
        zoo = program.get_model(program.model_config(c["name"], ref.program_kwargs(conf), settings))
        assert weights.shapes(ref.layout(conf)) == program.param_shapes(zoo)
        assert not math.isnan(ref.dims(conf).eps)

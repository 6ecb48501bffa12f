"""The benchmark's manifest (``BENCHMARK.json``) and the files it names.

Everything that belongs to one item sits in a file of its own and is found
by the item's name: a configuration is ``configs/<name>.json`` (the path the
manifest gives), a traffic mix ``traffic/<traffic>.json``, a cell's limits
``limits/<cell>.json``, a per-layer metric's reader
``metrics/<metric>.py``, a configuration's plain reference
``references/<reference>.py`` and a traffic kind's runner
``harness/kinds/<kind>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Optional[Dict[str, Any]]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(manifest: Dict[str, Any], name: str, root: Path = ROOT, bench: Path = BENCH) -> Cell:
    try:
        w = next(w for w in manifest["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    c = next(c for c in manifest["configs"] if c["name"] == w["config"])
    limits_file = bench / "limits" / f"{name}.json"
    return Cell(
        name=name, config_name=c["name"], traffic_name=w["traffic"], chips=int(w["chips"]),
        config=json.loads((root / c["file"]).read_text()),
        traffic=json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads(limits_file.read_text()) if limits_file.exists() else None,
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
    )


def reference(config: Dict[str, Any]):
    return importlib.import_module(f"references.{config['reference']}")


def kind(traffic: Dict[str, Any]):
    return importlib.import_module(f"harness.kinds.{traffic['kind']}")


def reader(metric: str, bench: Path = BENCH) -> Callable:
    path = bench / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read

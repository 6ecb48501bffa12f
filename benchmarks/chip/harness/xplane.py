"""Profiler trace (``.xplane.pb``) to plain events.

JAX's profiler writes one plane per TPU core (``/device:TPU:<n>``), whose
line ``XLA Ops`` holds every HLO instruction the core ran, named by its
HLO text, nested (a ``while`` loop's event spans its body's events), and a
host plane (``/host:CPU``) whose ``python3`` line holds the
``jax.profiler.TraceAnnotation`` spans of the main thread.  Device and host
events share one clock: nanoseconds from the start of the profile.

``load`` keeps what the reduction needs and nothing else, as plain
tuples, so that a trace can be stored as JSON and read back for tests.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINES = ("XLA Ops", "Async XLA Ops")
HOST_PREFIX = "bench."


class Event(NamedTuple):
    name: str
    start: float      # ns from the start of the profile
    end: float
    line: str


class Trace(NamedTuple):
    devices: Dict[int, List[Event]]   # core id -> op events (both op lines)
    host: List[Event]                 # the benchmark's own host spans


def find(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, found {files}")
    return files[0]


def load(path: str) -> Trace:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name in OPS_LINES:
                    evs.extend(Event(e.name, e.start_ns, e.end_ns, line.name) for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns, line.name)
                            for e in line.events if e.name.startswith(HOST_PREFIX))
    for evs in devices.values():
        evs.sort(key=lambda e: (e.start, -e.end))
    host.sort(key=lambda e: (e.start, -e.end))
    return Trace(devices, host)


def dump(trace: Trace, path: str) -> None:
    json.dump({"devices": {str(k): [list(e) for e in v] for k, v in trace.devices.items()},
               "host": [list(e) for e in trace.host]}, open(path, "w"))


def read(path: str) -> Trace:
    d = json.load(open(path))
    return Trace({int(k): [Event(*e) for e in v] for k, v in d["devices"].items()},
                 [Event(*e) for e in d["host"]])

"""What every kind of cell shares: the device check, the peak memory, a
guard against compilation inside the window, and the traced run."""

from __future__ import annotations

import contextlib
import os
import shutil
import tempfile
from typing import Any, Dict, List

import jax

from .. import xplane
from ..peaks import peak
from ..reduce import reduce

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_compiles: List[str] = []
_listening = False


def _listen(event: str, *args, **kwargs) -> None:
    if event == BACKEND_COMPILE:
        _compiles.append(str(kwargs.get("fun_name")))


@contextlib.contextmanager
def no_compiles():
    """Raise if anything is compiled (or loaded from the compilation
    cache) inside the block: the window must run warm programs only."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_listen)
        _listening = True
    before = len(_compiles)
    yield
    if len(_compiles) != before:
        raise RuntimeError(f"compiled inside the measured window: {_compiles[before:]}")


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def peak_of(devices) -> Dict[str, Any]:
    return peak(devices[0].device_kind)


class Tracer:
    """The JAX profiler around the window, writing under ``TMPDIR``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self) -> None:
        jax.profiler.start_trace(self.dir)

    def stop(self) -> str:
        jax.profiler.stop_trace()
        return self.dir


def reduce_trace(directory: str, ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Reduce the trace under ``directory`` (then deleted) and hand the
    result, with ``ctx``, to the per-layer metric readers."""
    try:
        trace = xplane.load(xplane.find(directory))
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    if os.environ.get("BENCH_TRACE_DUMP"):      # keep the events, e.g. for a test's recorded trace
        xplane.dump(trace, os.environ["BENCH_TRACE_DUMP"])
    return dict(ctx, reduced=reduce(trace))

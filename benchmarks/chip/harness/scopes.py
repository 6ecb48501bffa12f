"""Device time by the program's named scopes.

The program names its layers with ``jax.named_scope``; each HLO
instruction's ``op_name`` metadata holds the path of names it was traced
under, for example
``jit(step)/transpose(jvp(layers))/while/body/checkpoint/transpose(jvp(attention))/dot_general``.
The backward and recomputed copies of an op keep its forward's names
inside transform wrappers.  An op counts under the innermost of
``SCOPES`` on its path (an op under ``layers`` and not under
``attention`` or ``mlp`` is ``layers``'s own: the scan's carry, stacking
and remat stores); an op under none of them is ``unscoped``.

Pallas kernels carry their ``name=`` in the same path, just above
``pallas_call`` (``.../attention/flash_fwd_lse/pallas_call``): ``kernel_of``.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, Mapping, Optional

from . import reduce
from .xplane import Trace

SCOPES = ("embed", "layers", "attention", "mlp", "moe", "head", "kv_cache",
          "optimizer", "grad_reduce")
UNSCOPED = "unscoped"

_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def _unwrap(part: str) -> str:
    """``transpose(jvp(attention))`` -> ``attention``."""
    m = _WRAPPED.match(part)
    while m:
        part = m.group(1)
        m = _WRAPPED.match(part)
    return part


def scope_of(op_name: str) -> str:
    """The innermost scope on an ``op_name`` path, or ``unscoped``."""
    for part in reversed(op_name.split("/")):
        name = _unwrap(part)
        if name in SCOPES:
            return name
    return UNSCOPED


def kernel_of(op_name: str) -> Optional[str]:
    """The ``name=`` of the Pallas kernel an ``op_name`` path calls."""
    parts = [_unwrap(p) for p in op_name.split("/")]
    if len(parts) >= 2 and parts[-1] == "pallas_call":
        return parts[-2]
    return None


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` for every instruction of an HLO
    module's text that carries one (fused computations included)."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(line)
        if meta:
            out[m.group(1)] = meta.group(1)
    return out


def totals(seconds_by_op_name: Iterable[tuple]) -> Dict[str, float]:
    """Sum (op_name, seconds) pairs by scope; every scope is present."""
    out: Dict[str, float] = defaultdict(float)
    for name in SCOPES + (UNSCOPED,):
        out[name] = 0.0
    for op_name, seconds in seconds_by_op_name:
        out[scope_of(op_name or "")] += seconds
    return dict(out)


def shares(scope_s: Mapping[str, float], busy_core_s: float) -> Dict[str, float]:
    """Each scope's seconds as a percentage of the busy time of all cores."""
    return {k: 100.0 * v / busy_core_s for k, v in scope_s.items()}


def device_time(trace: Trace, names: Mapping[str, str]) -> Dict[str, float]:
    """Seconds by scope of every core's ``XLA Ops`` line, clipped to the
    window (``reduce``'s definitions): each op's own time, the part of its
    interval that no op nested in it covers.  That is all of a leaf op's
    time, and for a loop the time between its body's ops, which then
    counts under the loop's scope.  The scopes' seconds sum to the busy
    time of all cores.  ``names`` maps an HLO instruction's name to its
    ``op_name`` (``op_names``)."""
    lo, hi = reduce.window(trace)
    timed = []
    for evs in trace.devices.values():
        ops = [e for e in evs if e.line == "XLA Ops"]     # sorted by start, longest first
        for i, e in enumerate(ops):
            j = i + 1
            while j < len(ops) and ops[j].start < e.end:
                j += 1
            inner = reduce.clip([(c.start, c.end) for c in ops[i + 1:j]], lo, hi)
            own = reduce.minus(reduce.clip([(e.start, e.end)], lo, hi), inner)
            if own > 0:
                timed.append((names.get(reduce._split_instruction(e.name)[0], ""), own * 1e-9))
    return totals(timed)

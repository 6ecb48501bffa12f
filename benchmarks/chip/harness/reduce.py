"""Reduction of a trace to the numbers the per-layer metrics read.

Definitions (all clipped to the traced window, the host span
``bench.window``):

* busy: the union of the intervals of every event on a core's ``XLA Ops``
  line; idle share = 1 - busy / window, busy averaged over cores;
* leaf op: an ``XLA Ops`` event that contains no other event of its line
  (a ``while`` loop's event contains its body's ops and is not a leaf);
* flash kernel call: a leaf ``custom-call`` to ``tpu_custom_call`` with the
  signature of one of the flash kernels (see ``flash_kind``);
* collective: an op whose opcode is an all-reduce, all-gather,
  reduce-scatter, collective-permute or all-to-all (or its -start/-done),
  on either op line; exposed collective time = the union of a core's
  collective intervals minus the union of its other leaf ops;
* idle gap: a maximal interval of the window in which a core runs
  nothing, attributed to the innermost host span covering its middle.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from .xplane import Event, Trace

Interval = Tuple[float, float]
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(-start|-done)?$")
COLLECTIVE_NAME = re.compile(r"^%(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
SUFFIX = re.compile(r"\.\d+$")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def minus(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of union(a) not covered by union(b)."""
    a, b = union(a), union(b)
    out, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


# ---------------------------------------------------------------------------
# reading HLO text
# ---------------------------------------------------------------------------


def _split_instruction(name: str) -> Tuple[str, str, str, str]:
    """'%x.1 = SHAPE opcode(OPERANDS), attrs' -> (x.1, SHAPE, opcode, rest
    from the operand list on).  Parentheses inside layouts ({...}) are
    skipped."""
    head, sep, body = name.partition(" = ")
    if not sep:
        return name.lstrip("%"), "", "", ""
    depth_p = depth_b = 0
    for i, ch in enumerate(body):
        if ch == "{":
            depth_b += 1
        elif ch == "}":
            depth_b -= 1
        elif depth_b == 0 and ch == "(":
            depth_p += 1
        elif depth_b == 0 and ch == ")":
            depth_p -= 1
        elif ch == " " and depth_p == 0 and depth_b == 0:
            shape, rest = body[:i], body[i + 1:]
            op = rest.split("(", 1)[0]
            return head.lstrip("%"), shape, op, rest[len(op):]
    return head.lstrip("%"), body, "", ""


def _elements(shape: str) -> List[str]:
    """Element types of a result shape: 'bf16[2,4]{..}' or a tuple of them."""
    return re.findall(r"([a-z]+\d*)\[([\d,]*)\]", shape)


def opcode(name: str) -> str:
    return _split_instruction(name)[2]


def flash_kind(name: str) -> Optional[str]:
    """Which flash kernel a ``tpu_custom_call`` is, from its signature:
    ``fwd_lse`` returns (o, lse[..., 1]); ``bwd_dkv`` returns (dk, dv);
    ``bwd_dq`` returns dq from six operands; ``fwd`` returns o from
    three.  Only 4-D (batch, heads, seq, head_dim) results count."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    _, shape, op, rest = _split_instruction(name)
    if op != "custom-call":
        return None
    els = _elements(shape)
    if not els or any(len(dims.split(",")) != 4 for _, dims in els):
        return None
    operands = rest.split("), ", 1)[0].count("%")
    if len(els) == 2 and els[1][1].endswith(",1"):
        return "fwd_lse"
    if len(els) == 2:
        return "bwd_dkv"
    if len(els) == 1 and operands == 6:
        return "bwd_dq"
    if len(els) == 1 and operands == 3:
        return "fwd"
    return None


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(opcode(name)) or COLLECTIVE_NAME.match(name))


def group_name(name: str) -> str:
    """A stable name for the breakdown: the flash kernel, or the HLO
    instruction's name without its number."""
    kind = flash_kind(name)
    if kind:
        return f"flash.{kind}"
    return SUFFIX.sub("", _split_instruction(name)[0])


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events of one line that contain no other event of that line
    (events sorted by start, longest first on ties)."""
    out = []
    for i, e in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt.start >= e.end:
            out.append(e)
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


class Reduced(NamedTuple):
    window_s: float
    cores: int
    busy_s: float                          # mean over cores
    idle_share: float
    flash: Dict[str, Tuple[int, float]]    # kind -> (calls, seconds), all cores
    collective_s: float                    # mean over cores
    collective_exposed_s: float            # mean over cores
    device_ops: List[Tuple[str, float]]    # top 10, seconds, all cores
    idle_gaps: List[Tuple[str, float]]     # top 10 longest, seconds


def window(trace: Trace) -> Interval:
    spans = [e for e in trace.host if e.name == "bench.window"]
    if len(spans) != 1:
        raise ValueError(f"expected one bench.window host span, found {len(spans)}")
    return spans[0].start, spans[0].end


def _host_at(host: Sequence[Event], t: float) -> str:
    best = None
    for e in host:
        if e.start <= t < e.end and e.name != "bench.window":
            if best is None or e.end - e.start < best.end - best.start:
                best = e
    return best.name if best else "bench.window"


def reduce(trace: Trace, top: int = 10) -> Reduced:
    lo, hi = window(trace)
    if not trace.devices:
        raise ValueError("the trace has no TPU device plane")
    busy_sum = coll_sum = exposed_sum = 0.0
    flash: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    ops: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    for core, evs in sorted(trace.devices.items()):
        sync = [e for e in evs if e.line == "XLA Ops"]
        busy = union(clip([(e.start, e.end) for e in sync], lo, hi))
        busy_sum += total(busy)
        leaf = [e for e in leaves(sync) if e.end > lo and e.start < hi]
        coll, compute = [], []
        for e in leaf:
            s, t = max(e.start, lo), min(e.end, hi)
            kind = flash_kind(e.name)
            if kind:
                flash[kind][0] += 1
                flash[kind][1] += (t - s) * 1e-9
            if is_collective(e.name):
                coll.append((s, t))
            else:
                compute.append((s, t))
            ops[group_name(e.name)] += (t - s) * 1e-9
        coll += clip([(e.start, e.end) for e in evs
                      if e.line != "XLA Ops" and is_collective(e.name)], lo, hi)
        coll_sum += total(union(coll))
        exposed_sum += minus(coll, compute)
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                gaps.append((s - prev, _host_at(trace.host, (s + prev) / 2)))
            prev = max(prev, e)
    n = len(trace.devices)
    win = (hi - lo) * 1e-9
    busy_s = busy_sum * 1e-9 / n
    return Reduced(
        window_s=win, cores=n, busy_s=busy_s,
        idle_share=1.0 - busy_s / win if win > 0 else 0.0,
        flash={k: (int(v[0]), v[1]) for k, v in flash.items()},
        collective_s=coll_sum * 1e-9 / n,
        collective_exposed_s=exposed_sum * 1e-9 / n,
        device_ops=sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=[(name, d * 1e-9) for d, name in sorted(gaps, key=lambda g: -g[0])[:top]],
    )

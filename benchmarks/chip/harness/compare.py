"""The numbers that decide ``correct``, each against its limit.

Training (the first steps of the timed step against the reference's):

* ``loss_gap``: the largest |loss - reference loss| over the first steps,
  in nats;
* ``grad_gap``: over parameter leaves, the largest gap between the norm of
  the first gradient as the optimizer received it and the reference's,
  over the larger of the reference's norm of that leaf and of the median
  leaf (some gradients are all but zero);
* ``change_gap``: the same for the norm of each leaf's change over the
  first steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (Adam moves those by round-off alone).

Decode:

* ``logit_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best at that position;
* ``kv_gap``: over the positions the window wrote (every layer, K and V),
  the widest relative gap ||written - reference|| / ||reference|| of one
  position's K or V (all its heads), so that a write left out or put at
  another position shows even where the logits hardly feel it.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

EXCLUDE_BELOW = 1e-3


def worst_leaf_gap(prog: Sequence[float], ref: Sequence[float],
                   keep: Optional[Sequence[bool]] = None) -> float:
    med = float(np.median(ref))
    worst = 0.0
    for i, (p, r) in enumerate(zip(prog, ref)):
        if keep is not None and not keep[i]:
            continue
        gap = abs(p - r) / max(r, med)
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """prog/ref: {"losses": [...], "grad": [per leaf], "change": [per leaf]}."""
    losses = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    loss_gap = max(losses) if all(map(math.isfinite, losses)) else math.inf
    med = float(np.median(ref["grad"]))
    keep = [g >= EXCLUDE_BELOW * med for g in ref["grad"]]
    return {
        "loss_gap": loss_gap,
        "grad_gap": worst_leaf_gap(prog["grad"], ref["grad"]),
        "change_gap": worst_leaf_gap(prog["change"], ref["change"], keep),
    }


def logit_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """ref_logits (n, V) at n positions, tokens (n,) served there."""
    ref_logits = np.asarray(ref_logits, np.float64)
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, np.asarray(tokens)[:, None], axis=-1)[:, 0]
    gap = float(np.max(best - got))
    return gap if math.isfinite(gap) else math.inf


def kv_gap(written: np.ndarray, ref: np.ndarray) -> float:
    """written/ref (..., positions, kv_heads, head_dim); inf where there
    is no position to compare."""
    written = np.asarray(written, np.float64)
    ref = np.asarray(ref, np.float64)
    if ref.size == 0:
        return math.inf
    num = np.sqrt(np.sum(np.square(written - ref), axis=(-2, -1)))
    den = np.sqrt(np.sum(np.square(ref), axis=(-2, -1)))
    gap = float(np.max(num / den))
    return gap if math.isfinite(gap) else math.inf


def judge(numbers: Dict[str, float], limits: Optional[Dict]) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """(correct, [(name, value, limit)]).  A number without a limit (no
    limits file yet) is not correct."""
    rows = []
    for name, value in numbers.items():
        limit = limits[name]["limit"] if limits and name in limits else None
        rows.append((name, value, limit))
    ok = all(lim is not None and math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows

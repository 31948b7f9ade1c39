"""The comparison that decides ``correct``: numbers, each beside its limit.

Training compares norms leaf by leaf (the gap between the program's norm and
the reference's, never the norm of a difference: under Adam the sign of a
tiny gradient is noise), serving compares logits (the widest gap by which a
served token lies below the reference's best).
"""

from __future__ import annotations

import math
import statistics
import sys
from typing import Dict, Iterable, Optional


def rel_gap(program: float, reference: float) -> float:
    return abs(program - reference) / max(abs(reference), 1e-30)


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float],
                   skip: Iterable[str] = ()) -> float:
    """max over leaves of |program norm - reference norm| over the larger of
    the reference's norm of that leaf and of the median leaf."""
    skip = set(skip)
    if set(program) != set(reference):
        raise ValueError(f"leaf sets differ: {sorted(set(program) ^ set(reference))[:4]}")
    med = statistics.median(reference.values())
    worst = 0.0
    for name, ref in reference.items():
        if name in skip:
            continue
        base = max(ref, med, 1e-30)
        worst = max(worst, abs(program[name] - ref) / base)
    return worst


def worst_leaf_turn(program: dict, reference: dict, skip: Iterable[str] = ()) -> float:
    """max over leaves of 1 - cosine between the program's array and the
    reference's: how far a leaf's gradient has turned. First order in the
    noise of the arithmetic, where a gap of norms is second order, so it is
    the number that tells one precision from the next."""
    import numpy as np

    skip = set(skip)
    worst = 0.0
    for name, ref in reference.items():
        if name in skip:
            continue
        a = np.asarray(program[name], np.float64).ravel()
        b = np.asarray(ref, np.float64).ravel()
        den = float(np.linalg.norm(a) * np.linalg.norm(b))
        worst = max(worst, 1.0 if den == 0.0 else 1.0 - float(a @ b) / den)
    return worst


def still_leaves(first_grad: Dict[str, float], share: float = 1e-3) -> set:
    """Leaves whose first gradient in the REFERENCE is under ``share`` of the
    median leaf's: under Adam they move by round-off alone, so their change
    is not compared (a rule on the gradient, not on names)."""
    med = statistics.median(first_grad.values())
    return {k for k, v in first_grad.items() if v < share * med}


def widest_gap(ref_logits, served_tokens) -> float:
    """ref_logits [n, V] (numpy), served_tokens [n]: the most by which a
    served token's reference logit lies below the reference's best."""
    import numpy as np

    best = ref_logits.max(axis=-1)
    got = ref_logits[np.arange(len(served_tokens)), np.asarray(served_tokens)]
    return float((best - got).max())


class Verdict:
    """Collects (name, value, limit); ``correct`` iff every value is finite
    and at or under its limit. A limit of None means 'reported, not held'."""

    def __init__(self):
        self.rows: Dict[str, Dict[str, Optional[float]]] = {}

    def add(self, name: str, value: float, limit: Optional[float]) -> None:
        self.rows[name] = {"value": float(value), "limit": limit}

    @property
    def correct(self) -> bool:
        if not any(r["limit"] is not None for r in self.rows.values()):
            return False  # nothing was compared: that is not 'correct'
        for r in self.rows.values():
            if r["limit"] is None:
                continue
            if not math.isfinite(r["value"]) or r["value"] > r["limit"]:
                return False
        return True

    def print_stderr(self) -> None:
        for name, r in self.rows.items():
            held = "" if r["limit"] is None else (" ok" if r["value"] <= r["limit"] else " OVER")
            print(f"compared {name} = {r['value']!r} limit {r['limit']!r}{held}", file=sys.stderr)
        print(f"correct = {self.correct}", file=sys.stderr, flush=True)

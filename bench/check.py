"""The comparison that decides ``correct``.

What the timed path returned (a front's indices and objectives, the
per-(model, PE type) bests, the objectives a search observed) is set
against the plain reference (``bench.reference``, float64) over the same
design points.  Each comparison is one number with a limit of its own
(``limits.json``); a run is correct when every number is within its
limit.  The control is the same reference computed in bfloat16 (device
stages) and float32 (host columns), put in the program's place.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import ml_dtypes
import numpy as np

from bench.reference import joint

LIMITS = json.loads((Path(__file__).resolve().parent / "limits.json")
                    .read_text())
CONTROL_DTYPES = (ml_dtypes.bfloat16, np.float32)

# A budget bound is decided on float32 columns: a point within this
# relative distance of a bound may fall on either side of it.  The
# reference front is taken over points feasible by this margin, and a
# returned point may break a bound by it; ten times the float32 drift
# of the columns (about 1e-6) and a tenth of the limits.
BOUND_MARGIN = 1e-5


class Numbers:
    """Running maxima of the compared numbers over checked items."""

    def __init__(self):
        self.values: dict[str, float] = {}

    def add(self, name: str, value: float) -> None:
        v = float(value)
        if math.isnan(v):
            v = math.inf
        self.values[name] = max(self.values.get(name, 0.0), v)

    def merge(self, other: "Numbers") -> None:
        for k, v in other.values.items():
            self.add(k, v)

    def lines(self) -> list[tuple[str, float, float]]:
        return [(k, v, LIMITS[k]) for k, v in sorted(self.values.items())]

    def correct(self) -> bool:
        return all(v <= LIMITS[k] for k, v in self.values.items())


def front_numbers(ref: dict, budget: dict | None, got_idx: np.ndarray,
                  got_obj: np.ndarray, ref_idx: np.ndarray) -> Numbers:
    """A returned front against the reference columns ``ref`` of the
    same point set, whose joint indices are ``ref_idx`` (ascending)."""
    n = Numbers()
    got_idx = np.asarray(got_idx, np.int64)
    rows = np.minimum(np.searchsorted(ref_idx, got_idx), len(ref_idx) - 1)
    known = ref_idx[rows] == got_idx
    n.add("front_unknown_rows", int((~known).sum()))
    rows, got_obj = rows[known], np.asarray(got_obj, np.float64)[known]
    want = ref["objectives"][rows]
    n.add("obj_rel_err", joint.rel_err(got_obj, want))
    viol = joint.violation(ref, budget)
    n.add("front_excess", max(0.0, float(viol[rows].max()) - BOUND_MARGIN)
          if len(rows) else 0.0)
    feasible = np.flatnonzero(viol <= -BOUND_MARGIN) if budget \
        else np.arange(len(viol))
    ref_front = ref["objectives"][feasible[
        joint.pareto_front(ref["objectives"][feasible])]]
    n.add("front_miss", joint.cover_gap(ref_front, got_obj))
    n.add("front_excess", joint.excess_gap(ref_front, want))
    return n


def best_numbers(ref: dict, got_best: dict) -> Numbers:
    """Per-(model, PE type) best MACs/s/mm^2 and lowest pJ/MAC against
    the reference's over the same points; a group missing on either side
    counts in ``best_groups``."""
    n = Numbers()
    want = joint.per_model_best(ref)
    n.add("best_groups", len(set(want) ^ set(got_best)))
    for k in set(want) & set(got_best):
        n.add("best_rel_err", joint.rel_err(got_best[k], want[k]))
    return n


def control_front(models, space, idx: np.ndarray, budget: dict | None):
    """The control's answer for a point set: the reference evaluated in
    the control's precision, then its front over its own feasible set."""
    ctl = joint.evaluate(models, space, idx, *CONTROL_DTYPES)
    keep = np.flatnonzero(joint.violation(ctl, budget) <= 0) if budget \
        else np.arange(len(idx))
    f = keep[joint.pareto_front(ctl["objectives"][keep])]
    return ctl, idx[f], ctl["objectives"][f]

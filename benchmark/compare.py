"""The comparison that decides `correct` for emitted model rows.

A model is `{"feats": int64[n] sorted, "tables": {name: float[n(, k)]},
"scalars": {name: float}}`, from the program's `model_rows()` or from a
plain reference. Numbers compared, each against a limit of its own from the
configuration's file:

- `rows_diff`: features emitted by one side and not the other (exact: 0);
- `<table>_gap`: the widest |program - reference| of a table's entries, over
  |reference entry| + the table's median |reference entry| (a relative gap
  that does not blow up on entries that are all but zero);
- `<scalar>_gap`: |program - reference|;
- `logloss_gap`: |held-out logloss of the program's rows - of the
  reference's| on rows neither saw.
"""

from __future__ import annotations

from typing import Callable, Dict

import numpy as np


def table_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    if ref.size == 0:
        return 0.0
    ref = np.asarray(ref, np.float64)
    prog = np.asarray(prog, np.float64)
    floor = float(np.median(np.abs(ref)))
    gap = np.abs(prog - ref) / (np.abs(ref) + floor + 1e-300)
    worst = float(np.max(gap))
    return worst if np.isfinite(worst) else float("inf")


def logloss(scores: np.ndarray, labels01: np.ndarray) -> float:
    y = np.where(np.asarray(labels01) > 0, 1.0, -1.0)
    return float(np.mean(np.logaddexp(0.0, -y * scores)))


def model_gaps(prog: dict, ref: dict) -> Dict[str, float]:
    pf, rf = prog["feats"], ref["feats"]
    if pf.size == rf.size and np.array_equal(pf, rf):
        diff, pi, ri = 0, slice(None), slice(None)
    else:
        common, pi, ri = np.intersect1d(pf, rf, assume_unique=True,
                                        return_indices=True)
        diff = int(pf.size + rf.size - 2 * common.size)
    out: Dict[str, float] = {"rows_diff": float(diff)}
    for name, rt in ref["tables"].items():
        out[f"{name}_gap"] = table_gap(prog["tables"][name][pi], rt[ri])
    for name, rs in ref["scalars"].items():
        out[f"{name}_gap"] = abs(float(prog["scalars"][name]) - float(rs))
    return out


def heldout_gap(prog: dict, ref: dict, scorer: Callable, heldout) -> float:
    lp = logloss(scorer(prog, heldout.ids, heldout.vals), heldout.labels)
    lr = logloss(scorer(ref, heldout.ids, heldout.vals), heldout.labels)
    return abs(lp - lr)


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """{name: {"value", "limit", "ok"}} for every limit; a number that was
    not produced fails, and so does one that is not finite."""
    out = {}
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = value is not None and np.isfinite(value) and value <= limit
        out[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    return out

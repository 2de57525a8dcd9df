"""The comparisons that decide ``correct``. Each number compared is printed
beside its limit in every run (``comparison`` lines on standard output)."""

from __future__ import annotations

import json
from statistics import median


def worst_leaf_gap(program: dict, reference: dict) -> tuple:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    floor = median(reference.values())
    worst, where = 0.0, None
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, floor)
        if gap > worst or where is None:
            worst, where = gap, name
    return worst, where


def worst_leaf_difference(program: dict, reference: dict) -> tuple:
    """Over sampled elements of each leaf: the norm of the difference between
    the program's values and the reference's, against the norm of the
    reference's sample of that leaf or of the median leaf, whichever is larger.
    Rounding noise that leaves a norm where it was shows here in full."""
    import numpy as np

    norms = {n: float(np.linalg.norm(r)) for n, r in reference.items()}
    floor = median(norms.values())
    worst, where = 0.0, None
    for name, ref in reference.items():
        gap = float(np.linalg.norm(np.asarray(program[name], np.float64) - ref)) / max(norms[name], floor)
        if gap > worst or where is None:
            worst, where = gap, name
    return worst, where


class Comparison:
    """Collects ``(name, value, limit)`` and prints each as it is added."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value: float, limit: float, note: str = "") -> bool:
        ok = bool(value == value and value <= limit)
        self.rows.append({"name": name, "value": float(value), "limit": float(limit), "ok": ok})
        print("comparison " + json.dumps(
            {"name": name, "value": float(value), "limit": float(limit), "ok": ok, "note": note}
        ), flush=True)
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

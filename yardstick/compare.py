"""The numbers that decide ``correct``, and their limits.

A cell's limits are a file, ``yardstick/limits/<workload>.json``: every
number named there is compared, ``value <= limit``, and a number the file
does not name is not. ``PERF.md`` gives the readings each limit was set
from. The arithmetic is here so that the program, the control and a planted
fault are all read by the same lines.
"""

from __future__ import annotations

import numpy as np

from yardstick import harness

#: Leaves whose first gradient, in the reference, is under this share of
#: the median leaf's move under Adam by round-off alone: they are left out
#: of the change's comparison by this rule, not by name.
DEAD_GRADIENT = 1e-3


def worst_leaf_gap(got, ref, keep=None) -> float:
    """The widest gap between a leaf's norm here and in the reference,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    gap = np.abs(got - ref) / np.maximum(ref, np.median(ref))
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap))


def train_numbers(got: dict, ref: dict) -> dict[str, float]:
    """``got`` and ``ref``: ``losses`` (one a step), ``grad_norms`` and
    ``change_norms`` (one a leaf, the same order)."""
    out = {}
    for i, (a, b) in enumerate(zip(np.asarray(got["losses"], np.float64),
                                   np.asarray(ref["losses"], np.float64)),
                               start=1):
        out[f"loss_step{i}"] = float(abs(a - b) / abs(b))
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    out["first_grad_norm"] = worst_leaf_gap(got["grad_norms"], g_ref)
    moved = g_ref >= DEAD_GRADIENT * np.median(g_ref)
    out["change_norm"] = worst_leaf_gap(
        got["change_norms"], ref["change_norms"], keep=moved)
    return out


def load_limits(workload: str) -> dict[str, float]:
    return harness.load_json(harness.HERE / "limits" / f"{workload}.json")[
        "limits"]


def against_limits(numbers: dict[str, float],
                   limits: dict[str, float]) -> list[harness.Compared]:
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise KeyError(f"limits name numbers that were not read: {missing}")
    return [harness.Compared(k, numbers[k], limits[k]) for k in limits]

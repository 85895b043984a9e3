"""The comparison that decides ``correct``.

Five numbers, each held to a limit of its own (the limits, and the
readings they were set from, are in ``PERF.md``):

``loss_gap``          the largest relative gap between the program's
                      loss and the reference's over the first steps;
``select_gap``        the selection: the first aggregated gradient, read
                      from the momentum after one step, counted per leaf
                      (its nonzero coordinates); for each leaf the gap
                      between the program's count and the reference's
                      over the reference's count of that leaf or of the
                      median leaf, whichever is larger; the worst leaf's;
``update_gap``        the same first aggregated gradient by its leaf
                      norms, by the same worst-leaf measure;
``first_change_gap``  the parameters' change after the first step, as
                      the optimizer applied it, by the same worst-leaf
                      measure;
``change_gap``        the parameters' change over all the first steps,
                      by the same per-leaf measure, the median leaf's.

The per-leaf numbers are taken over the leaves whose Gaussian-k budget
is at least ``MIN_BUDGET`` coordinates, the change numbers also leaving
out the leaves whose reference gradient is under a thousandth of the
median leaf's (a leaf with no gradient moves by round-off alone).  In a
smaller leaf (a norm scale of a few thousand elements selects a handful)
one decision of Algorithm 1's refinement, which a rounding can flip,
moves the whole leaf's selection: the program selects none where the
reference selects 8, or the reverse, on some seeds.  The same holds for
a large leaf at the later steps: after the first, the LM head's
threshold can end a factor of 1.5 or 2 away on the two sides, which
moves its change over three steps by about 27% on some seeds.  So the
change over all the first steps is read at the median leaf, and the
worst leaf is read after the first step.
"""
from __future__ import annotations

import numpy as np

NAMES = ("loss_gap", "select_gap", "update_gap", "first_change_gap",
         "change_gap")
MIN_BUDGET = 100


def leaf_gaps(prog, ref, keep=None) -> np.ndarray:
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers, from the program's (or the control's) first
    steps and the reference's, both as ``reference.first_steps`` names
    them."""
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        return {n: float("inf") for n in NAMES}
    g = np.asarray(ref["grad_norms"], float)
    big = np.asarray(ref["budgets"]) >= MIN_BUDGET
    moved = big & (g >= 1e-3 * np.median(g))
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "select_gap": float(np.max(leaf_gaps(prog["update_counts"],
                                             ref["update_counts"], big))),
        "update_gap": float(np.max(leaf_gaps(prog["update_norms"],
                                             ref["update_norms"], big))),
        "first_change_gap": float(np.max(leaf_gaps(
            prog["first_change_norms"], ref["first_change_norms"], moved))),
        "change_gap": float(np.median(leaf_gaps(prog["change_norms"],
                                                ref["change_norms"], moved))),
    }


def leaf_lines(prog: dict, ref: dict, names) -> list:
    """For the reader of a run's log: one line per leaf with the
    program's and the reference's count, update norm and change norms."""
    keys = ("update_counts", "update_norms", "first_change_norms",
            "change_norms")
    return [f"{name}: " + "; ".join(
        f"{k} {float(prog[k][i])!r} vs {float(ref[k][i])!r}" for k in keys)
        for i, name in enumerate(names)]


def verdict(read: dict, limits: dict):
    """(correct, checks): every number at or under its limit; ``checks``
    lists each as ``[name, value, limit]``.  A number that is not finite
    fails."""
    checks = [[n, read[n], limits[n]] for n in NAMES]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in checks)
    return bool(ok), checks

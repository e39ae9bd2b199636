"""The arithmetic of ``correct``: each number compared, beside its limit.

Training numbers are gaps between NORMS (the program's norm against the
reference's, not the norm of a difference), taken by the worst leaf and
measured against the reference's norm of that leaf or of the median
leaf, whichever is larger, because some gradients are all but zero.
"""

from __future__ import annotations

import numpy as np


def _flat(tree):
    return {f"{layer}.{leaf}": np.asarray(v, np.float64)
            for layer, leaves in tree.items() for leaf, v in leaves.items()}


def leaf_norms(tree):
    return {k: float(np.linalg.norm(v)) for k, v in _flat(tree).items()}


def tree_sub(a, b):
    return {layer: {leaf: np.asarray(a[layer][leaf], np.float64)
                    - np.asarray(b[layer][leaf], np.float64) for leaf in leaves}
            for layer, leaves in a.items()}


def norm_gaps(prog, ref, keep=None):
    """{leaf: gap} of two {leaf: norm} dicts: |program - reference| over
    the reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    names = [k for k in ref if keep is None or k in keep]
    median = float(np.median([ref[k] for k in names])) if names else 0.0
    out = {}
    for k in names:
        gap = abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
        if median == 0.0 and prog[k] < 1e-6:
            gap = 0.0  # no leaf moves in the reference (an empty mining set)
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def worst_and_median(gaps):
    """(worst gap, its leaf, the median leaf's gap)."""
    where = max(gaps, key=gaps.get)
    return gaps[where], where, float(np.median(list(gaps.values())))


def error_norms(prog, ref, keep=None):
    """{leaf: ||program - reference|| / max(||reference||, median)}: the
    norm of the DIFFERENCE.  Rounding that is incoherent with the signal
    moves a norm only in second order but shows here in first, which is
    what separates a float8 trunk from a bfloat16 one."""
    ref_n = leaf_norms(ref)
    diff_n = leaf_norms(tree_sub(prog, ref))
    names = [k for k in ref_n if keep is None or k in keep]
    median = float(np.median([ref_n[k] for k in names])) if names else 0.0
    return {k: diff_n[k] / max(ref_n[k], median, 1e-30) for k in names}


def moving_leaves(ref_grad_norms, floor=1e-3):
    """Leaves whose reference gradient is not nought to rounding: at
    least ``floor`` of the median leaf's.  The others (a key's bias under
    softmax) move by round-off alone and are left out of the change."""
    median = float(np.median(list(ref_grad_norms.values())))
    return {k for k, v in ref_grad_norms.items() if v >= floor * median}


def training_numbers(prog, ref):
    """``prog``/``ref``: dicts with ``losses`` (list), ``grad`` (first
    gradient tree), ``delta`` (parameter change tree), in the plain layout."""
    loss_gap = max(abs(p - r) / max(abs(r), 1e-30)
                   for p, r in zip(prog["losses"], ref["losses"]))
    if not np.isfinite(loss_gap):
        loss_gap = float("inf")
    rg = leaf_norms(ref["grad"])
    grad_gap, grad_leaf, grad_mid = worst_and_median(
        norm_gaps(leaf_norms(prog["grad"]), rg))
    keep = moving_leaves(rg)
    delta_gap, delta_leaf, delta_mid = worst_and_median(norm_gaps(
        leaf_norms(prog["delta"]), leaf_norms(ref["delta"]), keep))
    grad_err = float(np.median(list(error_norms(prog["grad"], ref["grad"]).values())))
    delta_err = float(np.median(list(
        error_norms(prog["delta"], ref["delta"], keep).values())))
    return ({"loss_gap": float(loss_gap), "grad_gap": float(grad_gap),
             "delta_gap": float(delta_gap), "grad_gap_median": grad_mid,
             "delta_gap_median": delta_mid, "grad_err_median": grad_err,
             "delta_err_median": delta_err},
            {"grad_leaf": grad_leaf, "delta_leaf": delta_leaf,
             "left_out": sorted(set(rg) - keep)})


def judge(numbers: dict, limits: dict):
    """[(name, value, limit, ok)] and the verdict.  Every limit named in
    the cell's traffic file must have its number; a NaN fails."""
    rows = []
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = value is not None and np.isfinite(value) and value <= limit
        rows.append((name, value, limit, bool(ok)))
    return rows, all(r[3] for r in rows) and bool(rows)

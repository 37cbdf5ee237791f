"""The comparison that decides ``correct`` for a cell that trains in rounds.

The system's first rounds (the very object the window then drives) against
the plain reference's, from the same weights, data and batch order:

``loss_<k>``   round k's mean training loss: |system - reference| / reference
``grad_1``     the first aggregate the server applies (weights after round 1
               minus the start), leaf by leaf: the gap between the system's
               norm and the reference's, over the reference's norm of that
               leaf or of the median leaf, whichever is larger; worst leaf
``change_<K>`` the same for the weights' change after K rounds, leaving out
               the leaves whose first aggregate in the reference is under a
               thousandth of the median leaf's (they move by round-off alone)
``*_median``   the median leaf's gap of the two above: steady from seed to
               seed where the worst leaf is one small, noisy leaf

Each number has a limit of its own in ``cells/<cell>.json``.
"""

from __future__ import annotations

import numpy as np


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, np.asarray(tree, np.float64)


def delta_norms(after, before):
    """{leaf path: ||after - before||}"""
    b = dict(_leaves(before))
    return {k: float(np.linalg.norm(a - b[k])) for k, a in _leaves(after)}


def leaf_gaps(system, reference, leave_out=()):
    """{leaf: gap} over the leaves not left out."""
    med = float(np.median(list(reference.values())))
    out = {}
    for k, r in reference.items():
        if k not in leave_out:
            gap = abs(system[k] - r) / max(r, med, 1e-30)
            out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def worst_leaf_gap(system, reference, leave_out=()):
    """-> (gap, leaf, the median leaf's gap)."""
    gaps = leaf_gaps(system, reference, leave_out)
    where = max(gaps, key=gaps.get)
    return gaps[where], where, float(np.median(list(gaps.values())))


def compare(system, reference):
    """``system`` / ``reference``: {"losses": [K floats], "p0", "p1", "pK"}.
    -> ({number: value}, {number: the leaf it was read on})."""
    k_rounds = len(reference["losses"])
    numbers, where = {}, {}
    for k, (s, r) in enumerate(zip(system["losses"], reference["losses"]), 1):
        gap = abs(s - r) / abs(r) if np.isfinite(s) and r else float("inf")
        numbers[f"loss_{k}"] = gap
    ref_g = delta_norms(reference["p1"], reference["p0"])
    sys_g = delta_norms(system["p1"], system["p0"])
    (numbers["grad_1"], where["grad_1"],
     numbers["grad_1_median"]) = worst_leaf_gap(sys_g, ref_g)
    med = float(np.median(list(ref_g.values())))
    still = {k for k, g in ref_g.items() if g < 1e-3 * med}
    ref_c = delta_norms(reference["pK"], reference["p0"])
    sys_c = delta_norms(system["pK"], system["p0"])
    name = f"change_{k_rounds}"
    (numbers[name], where[name],
     numbers[name + "_median"]) = worst_leaf_gap(sys_c, ref_c, still)
    where["left_out"] = sorted(still)
    return numbers, where


def verdict(numbers, limits):
    """-> (correct, {number: {"value", "limit"}}) over the numbers the cell's
    file gives a limit; a limit whose number is missing fails."""
    table, ok = {}, True
    for k, lim in limits.items():
        v = numbers.get(k)
        table[k] = {"value": v, "limit": lim}
        if v is None or not (v <= lim):
            ok = False
    return ok, table

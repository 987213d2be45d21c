"""The comparison that decides ``correct`` for a training cell.

The program's first three steps (taken through the window's own call,
on the window's own batches) against the plain float32 reference run
from the same weights over the same batches.  Compared: each step's
loss; the norm of the first gradient as the optimizer got it (worked
out from the momentum after one step: ``m1 = -lr * g``); the norm of
each leaf's change after the three steps.  The norms go by the worst
leaf: the gap between the two NORMS (not the norm of the difference),
against the reference's norm of that leaf or of the median leaf,
whichever is larger.  Leaves whose reference gradient is under a
thousandth of the median leaf's (a bias in front of BatchNorm) move by
round-off alone and are left out of the change.  The same gaps are also
given for the MEDIAN leaf, and, where the driver names each leaf's kind
(a convolution's weight, a BatchNorm gamma), for the worst and the
median leaf of every kind as ``change_gap.<kind>`` and
``change_gap_median.<kind>``: where the worst leaf of all is noise (see
``PERF.md``), a cell holds each kind to limits of its own.  A cell
compares the numbers its ``limits`` name.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional

STEPS = 3
DEAD_GRADIENT = 1e-3


def readings(losses: List[float], first_momenta: Dict, lr: float,
             changes: Dict) -> Dict:
    """One side's numbers, as plain floats."""
    return {"losses": [float(v) for v in losses],
            "grad_norms": {k: float(v) / lr
                           for k, v in first_momenta.items()},
            "change_norms": {k: float(v) for k, v in changes.items()}}


def _gaps(prog: Dict, ref: Dict, keep) -> Dict[str, float]:
    floor = statistics.median(ref.values())
    return {k: abs(prog[k] - r) / max(r, floor) for k, r in ref.items()
            if keep(k)}


def numbers(prog: Dict, ref: Dict,
            kinds: Optional[Dict[str, str]] = None) -> Dict[str, float]:
    """``{name: value}`` of every number that can be compared;
    ``kinds`` is ``{leaf: kind}`` where the driver tells them apart."""
    g_floor = DEAD_GRADIENT * statistics.median(ref["grad_norms"].values())
    moving = {k for k, g in ref["grad_norms"].items() if g >= g_floor}
    gaps = {"grad_gap": _gaps(prog["grad_norms"], ref["grad_norms"],
                              lambda k: True),
            "change_gap": _gaps(prog["change_norms"], ref["change_norms"],
                                moving.__contains__)}
    out = {"loss_gap": max(abs(p - r) / abs(r) for p, r in
                           zip(prog["losses"], ref["losses"]))}
    for what, by_leaf in gaps.items():
        groups = {"": list(by_leaf.values())}
        for leaf, gap in by_leaf.items():
            if kinds:
                groups.setdefault("." + kinds[leaf], []).append(gap)
        for suffix, mine in groups.items():
            out[what + suffix] = max(mine)
            out[what + "_median" + suffix] = statistics.median(mine)
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """``{name: {value, limit}}`` for every number that has a limit."""
    return {k: {"value": values[k], "limit": limits[k]}
            for k in limits}

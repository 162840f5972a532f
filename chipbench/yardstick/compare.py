"""The numbers that decide ``correct``: the program's first steps against
the reference's. Every number below is computed and logged; those that
a cell's limits file names are compared, each with its own limit.

* ``loss_gap``: the largest |loss_program - loss_reference| over the
  steps followed, in nats.
* ``grad_norm_gap``: per leaf and replica, the gap between the norms of
  the first gradient (program: read from its momentum after one step,
  m / (1 - beta)), as a share of the reference's norm of that leaf or of
  the median leaf, whichever is larger; the worst leaf.
* ``delta_norm_gap``: the same for the norm of each leaf's change over
  all the steps followed. Leaves whose reference gradient is under a
  thousandth of the median leaf's (nought to rounding) are left out.
* ``replicas_differ`` (several replicas): leaves whose copies on the
  replicas' chips are not bit for bit the same; the reference's never
  differ.

A reading that is not finite is infinite, and fails its limit.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

NEGLIGIBLE = 1e-3


def _finite(x: float) -> float:
    return float(x) if math.isfinite(float(x)) else math.inf


def _leaf_gaps(prog: Dict[str, list], ref: Dict[str, list], keep
               ) -> Dict[str, float]:
    """Per leaf, the worst replica's gap of the norms over the larger of
    the reference's norm of that leaf and of the median leaf."""
    keys = [k for k in ref if k in keep]
    med = float(np.median([v for k in keys for v in ref[k]]))
    return {k: max(_finite(abs(p - r) / max(r, med, 1e-30))
                   for p, r in zip(prog[k], ref[k])) for k in keys}


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """`prog` and `ref` hold ``loss`` (per step), ``grad_norm`` ({leaf:
    [per replica]}) and ``delta_norm`` ({leaf: norm}); on several
    replicas `prog` holds ``replicas_differ``."""
    out = {"loss_gap": max(_finite(abs(p - r)) for p, r in
                           zip(prog["loss"], ref["loss"]))}
    out["grad_norm_gap"] = max(_leaf_gaps(
        prog["grad_norm"], ref["grad_norm"], set(ref["grad_norm"])).values())
    top = {k: max(v) for k, v in ref["grad_norm"].items()}
    med = float(np.median(list(top.values())))
    moving = {k for k, v in top.items() if v >= NEGLIGIBLE * med}
    out["delta_norm_gap"] = max(_leaf_gaps(
        {k: [v] for k, v in prog["delta_norm"].items()},
        {k: [v] for k, v in ref["delta_norm"].items()}, moving).values())
    if "replicas_differ" in prog:
        out["replicas_differ"] = float(prog["replicas_differ"])
    return out


def per_leaf(prog: Dict, ref: Dict) -> Dict[str, Dict[str, float]]:
    """Each leaf's first-gradient norms (replica 0) and their gap as a
    share of the reference's, for the log."""
    out = {}
    for k, r in ref["grad_norm"].items():
        p = prog["grad_norm"][k][0]
        out[k] = {"grad_prog": p, "grad_ref": r[0],
                  "gap": abs(p - r[0]) / max(r[0], 1e-30)}
    return out


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(nums[k] <= limits[k] for k in limits)

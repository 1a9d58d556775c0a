"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against the plain reference run over the same
recorded rounds from the same seed:

* ``loss_gap``: the largest relative gap of a round's loss;
* ``grad_gap``: the first round's gradient as the optimizer got it,
  leaf by leaf, as the gap between the program's norm and the reference's
  (not the norm of their difference), over the larger of the reference's
  norm of that leaf and of the median leaf;
* ``change_gap``: the same gap for the parameters' change over the
  recorded rounds.

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of both: nothing but round-off gives them a gradient
or moves them (a convolution bias ahead of BatchNorm is one, since the
normalization removes it), so their norms compare rounding noise.

A leaf is one parameter tensor of one site.  A number's reading is its
worst leaf.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

#: reference-gradient share of the median leaf under which a leaf is
#: left out
STILL_LEAF = 1e-3


def split_sites(tree: Dict[str, np.ndarray], stacked: bool
                ) -> Dict[str, np.ndarray]:
    """One entry per (leaf, site) where the leaves carry a site axis."""
    if not stacked:
        return {k: np.asarray(v, np.float64) for k, v in tree.items()}
    return {f"{k}@{s}": np.asarray(v[s], np.float64)
            for k, v in tree.items() for s in range(v.shape[0])}


def leaf_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              keep: Optional[Iterable[str]] = None) -> Dict[str, float]:
    """Each leaf's gap of norms."""
    names = sorted(ref) if keep is None else sorted(keep)
    rn = {k: float(np.linalg.norm(ref[k])) for k in names}
    med = float(np.median(list(rn.values())))
    out = {}
    for k in names:
        gap = abs(float(np.linalg.norm(prog[k])) - rn[k]) / max(rn[k], med,
                                                               1e-30)
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def norm_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             keep: Optional[Iterable[str]] = None) -> Tuple[float, str]:
    """(worst gap, its leaf) of per-leaf norms."""
    gaps = leaf_gaps(prog, ref, keep)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def training_gaps(prog: Dict, ref: Dict, *,
                  stacked: Dict[str, bool]) -> Dict:
    """Readings of the three numbers; ``prog`` and ``ref`` hold
    ``losses``, ``grad0``, ``params0`` and ``params_end``; ``stacked``
    says whether ``grad0`` and ``params`` carry a leading site axis."""
    pl = np.asarray(prog["losses"], np.float64)
    rl = np.asarray(ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(pl - rl) / np.abs(rl)))
    if not np.isfinite(loss_gap):
        loss_gap = float("inf")
    pg, rg = (split_sites(t["grad0"], stacked["grad0"]) for t in (prog, ref))
    rgn = {k: float(np.linalg.norm(v)) for k, v in rg.items()}
    med = float(np.median(list(rgn.values())))
    moving = [k for k, n in rgn.items() if n >= STILL_LEAF * med]
    grad_gap, grad_leaf = norm_gap(pg, rg, moving)
    delta = lambda t: split_sites(
        {k: t["params_end"][k] - t["params0"][k] for k in t["params0"]},
        stacked["params"])
    dp, dr = delta(prog), delta(ref)
    if stacked["params"] == stacked["grad0"]:
        keep = moving
    else:                        # a leaf moves where any site's gradient does
        leaf = lambda k: k.split("@")[0]
        keep = [k for k in dr if leaf(k) in {leaf(m) for m in moving}]
    change_gap, change_leaf = norm_gap(dp, dr, keep)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap, "grad_leaf": grad_leaf,
            "change_leaf": change_leaf,
            "still_leaves": sorted(set(rgn) - set(moving))}


def passes(checks) -> bool:
    """``correct``: every compared number finite and within its limit."""
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks)

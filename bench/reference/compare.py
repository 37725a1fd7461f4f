"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against the plain reference run from the same seed on
the same partitions in the same order:

* ``loss_gap``: the largest relative gap between the program's and the
  reference's loss over the first three steps;
* ``grad_gap``: the first step's gradient, as the optimizer got it (read
  back from its first moment after one step, m1 / (1 - b1)), compared by the
  worst leaf: |‖g_prog‖ − ‖g_ref‖| over the larger of the reference leaf's
  norm and the median leaf norm;
* ``change_gap``: the weights' change over the three steps, compared the
  same way.  Leaves whose first reference gradient is under a thousandth of
  the median leaf's move by round-off alone under AdamW and are left out.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

SMALL_GRAD = 1e-3


def _norms(tree: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def leaf_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              keep=None) -> Dict[str, float]:
    """Per leaf |‖prog‖ − ‖ref‖| / max(‖ref‖, median ‖ref‖)."""
    pn, rn = _norms(prog), _norms(ref)
    med = float(np.median(list(rn.values())))
    return {k: abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30)
            for k in rn if keep is None or k in keep}


def leaf_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             keep=None) -> Tuple[float, str]:
    """The worst leaf's gap, and which leaf it was."""
    gaps = leaf_gaps(prog, ref, keep)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def moved_leaves(grad1_ref: Dict[str, np.ndarray]) -> List[str]:
    """Leaves whose first reference gradient is not nought to rounding."""
    n = _norms(grad1_ref)
    med = float(np.median(list(n.values())))
    return [k for k, v in n.items() if v >= SMALL_GRAD * med]


def readings(prog: dict, ref: dict) -> Dict[str, float]:
    """The three compared numbers (and where the worst leaf was)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    grad_gap, grad_leaf = leaf_gap(prog["grad1"], ref["grad1"])
    keep = moved_leaves(ref["grad1"])
    change_gap, change_leaf = leaf_gap(prog["delta"], ref["delta"], keep)
    return dict(loss_gap=loss_gap, grad_gap=grad_gap, change_gap=change_gap,
                grad_leaf=grad_leaf, change_leaf=change_leaf,
                n_left_out=len(ref["grad1"]) - len(keep),
                loss_gap_steps=[float(x) for x in np.abs(lp - lr)
                                / np.abs(lr)],
                grad_gap_median=float(np.median(list(
                    leaf_gaps(prog["grad1"], ref["grad1"]).values()))),
                change_gap_median=float(np.median(list(
                    leaf_gaps(prog["delta"], ref["delta"], keep).values()))))

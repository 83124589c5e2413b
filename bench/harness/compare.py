"""The comparison that decides ``correct``.

Three numbers of the compressed steps that set-up drives through the
timed program after its warmup, each held to its limit where the cell's
``bench/limits/<cell>.json`` gives one:

* ``loss``: the largest relative gap between the program's and the
  reference's loss, over those steps;
* ``grad``: the gradient the first of them got, as the optimizer got it
  (the program's is read from its state before and after the step): by
  the worst leaf, the gap between the program's and the reference's norm
  of that leaf, over the larger of the reference's norm of the leaf and
  of the median leaf;
* ``change``: the same measure of the parameters' change over those
  steps.

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of both: Adam moves such a leaf by round-off alone.
The change leaves out the elements whose reference second moment is
exactly 0 after the warmup (their gradient was 0 at every warmup step:
the rows of the vocabulary's padding), which move by the momentum over
eps.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

QUIET_LEAF = 1e-3


def leaf_norms(flat: np.ndarray, sizes: Sequence[int],
               keep: np.ndarray = None) -> np.ndarray:
    out, off = [], 0
    for n in sizes:
        seg = flat[off:off + n].astype(np.float64)
        if keep is not None:
            seg = seg[keep[off:off + n]]
        out.append(np.sqrt(np.dot(seg, seg)))
        off += n
    return np.array(out)


def moving_leaves(ref_grad_norms: np.ndarray) -> np.ndarray:
    return ref_grad_norms >= QUIET_LEAF * np.median(ref_grad_norms)


def leaf_gaps(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray
              ) -> np.ndarray:
    """Each leaf's gap of norms over the larger of its reference norm and
    the median kept leaf's; -1 for a leaf left out."""
    floor = np.median(ref[keep])
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    return np.where(keep, gaps, -1.0)


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray
                   ) -> Tuple[float, int]:
    """(gap, leaf index) of the worst kept leaf."""
    gaps = leaf_gaps(prog, ref, keep)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    if len(prog) != len(ref):
        raise ValueError(f"{len(prog)} program losses, {len(ref)} "
                         "reference losses")
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if not np.all(np.isfinite(p)):
        return float("inf")
    return float(np.max(np.abs(p - r) / np.abs(r)))


def readings(prog: Dict, ref: Dict, sizes: Sequence[int],
             names: List[str]) -> Dict[str, dict]:
    """The numbers compared.  ``prog`` and ``ref`` each hold ``losses``
    (every followed compressed step), ``grad`` (the flat gradient of the
    first) and ``change`` (the flat parameter change over them); ``ref``
    also holds ``v``, its second moment after the warmup."""
    g_ref = leaf_norms(ref["grad"], sizes)
    keep = moving_leaves(g_ref)
    grad, gi = worst_leaf_gap(leaf_norms(prog["grad"], sizes), g_ref, keep)
    live = ref["v"] > 0
    change, ci = worst_leaf_gap(leaf_norms(prog["change"], sizes, live),
                                leaf_norms(ref["change"], sizes, live), keep)
    return {"loss": {"value": loss_gap(prog["losses"], ref["losses"])},
            "grad": {"value": grad, "leaf": names[gi]},
            "change": {"value": change, "leaf": names[ci]},
            "left_out": [n for n, k in zip(names, keep) if not k]}


def judge(read: Dict[str, dict], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """``correct`` and, per number the cell's limits hold, its value
    beside its limit."""
    checks = {k: {"value": read[k]["value"], "limit": float(limits[k])}
              for k in ("loss", "grad", "change") if k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks

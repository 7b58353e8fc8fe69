"""The comparison that decides ``correct``.

The program's readings (each set-up step's loss, the first global
gradient per leaf as AdamW got it, the parameters' change after the
three set-up steps per leaf, and the error-feedback state per node and
leaf) are held against the float32 reference's.  The gradient is
compared on the leaves whose global gradient is the exchanged gradient
itself (every leaf under ``none``; the dense-exempt and top-k-only
leaves under LGC): through the untrained autoencoder a leaf's gradient
is a reconstruction whose values follow the top-k support, and a
support changed at its margin by rounding moves them by up to the
leaf's own size (PERF.md, Findings).  A norm is compared by
its gap, |program - reference|, over the larger of the reference's norm
of that leaf and of the median leaf, and each number is the worst leaf.
The change leaves out leaves whose reference gradient is under a
thousandth of the median leaf's: under Adam they move by round-off.
It also leaves out leaves that send fewer than 100 top-k picks a step:
such a leaf moves by the Adam steps of its few picked elements, whose
values come through the autoencoder and follow the sent elements of
the large leaves, which rounding changes on every seed; with so few
elements to average over, its norm moves by up to a third (PERF.md,
Findings).  The error-feedback state is compared on every leaf.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

# the numbers compared, in the order they are printed
NUMBERS = ("loss", "grad", "change", "ef")
MOVED = 1e-3          # a leaf moves if its reference gradient is above this
                      # share of the median leaf's
MIN_PICKS = 100       # top-k picks a step for a steady change


def leaf_gaps(prog, ref) -> np.ndarray:
    """Each leaf's |program - reference| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    denom = np.maximum(ref, np.median(ref, axis=-1, keepdims=True))
    return np.abs(prog - ref) / np.where(denom > 0, denom, 1.0)


def worst_gap(prog, ref, keep=None) -> float:
    gap = leaf_gaps(prog, ref)
    if keep is not None:
        gap = gap[..., keep]
    return float(gap.max())


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers from the two sets of readings."""
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    out = {"loss": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    raw = np.asarray(ref["raw"])
    out["grad"] = worst_gap(prog["grad"], ref["grad"], keep=raw)
    picks = np.asarray(ref["picks"])
    steady = (picks == 0) | (picks >= MIN_PICKS)
    g = np.asarray(ref["grad"])
    out["change"] = worst_gap(prog["change"], ref["change"],
                              keep=steady & (g >= MOVED * np.median(g)))
    if "ef_u" in ref:
        out["ef"] = max(worst_gap(prog["ef_u"], ref["ef_u"]),
                        worst_gap(prog["ef_v"], ref["ef_v"]))
    if not all(np.isfinite(v) for v in out.values()):
        out = {k: (v if np.isfinite(v) else float("inf"))
               for k, v in out.items()}
    return out


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is within its limit.  A number without
    a limit cannot pass."""
    return all(k in limits and v <= limits[k] for k, v in nums.items())

"""The numbers that decide ``correct`` for a training cell.

Each reads the program's first training steps against the plain reference's
on the same weights and batches:

* ``loss_gap``: the largest relative gap between the two losses over the
  steps;
* ``grad_gap``: over the parameter leaves, the largest gap between the
  norms of the first gradient as the optimizer takes it (after clipping),
  each measured against the reference's norm of that leaf or the median
  leaf's, whichever is larger (some gradients are all but zero);
* ``change_gap``: the same for the norm of each leaf's change over the
  steps, leaving out leaves whose raw reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).

A gap of norms, not the norm of a difference: the two sides round
differently, element by element, and agree on the magnitude.
"""

from __future__ import annotations

import math
import statistics

# leaves whose first reference gradient is below this share of the median
# leaf's take no part in change_gap
STILL_LEAF_SHARE = 1e-3


def _worst_leaf(prog: dict, ref: dict, keep=None) -> float:
    names = [n for n in ref if keep is None or keep(n)]
    if set(prog) != set(ref):
        raise ValueError(f"leaf sets differ: {sorted(set(prog) ^ set(ref))[:5]}")
    med = statistics.median(ref[n] for n in names)
    return max(_finite(abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30))
               for n in names)


def _finite(x: float) -> float:
    """A gap that is not a number (a NaN loss or norm) is as wide as any."""
    return x if math.isfinite(x) else math.inf


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Per leaf: the reference's norm as a share of the median leaf's, and
    the gap of norms against the leaf's own norm. Not compared: the
    readings print it, to show which leaves the median floor measures
    against more than their own norm."""
    med = statistics.median(ref.values())
    return {n: {"share": ref[n] / med,
                "own": _finite(abs(prog[n] - ref[n]) / max(ref[n], 1e-30))}
            for n in ref}


def gaps(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` as ``train_readings`` returns them."""
    if len(prog["loss"]) != len(ref["loss"]):
        raise ValueError("the two sides took different numbers of steps")
    loss_gap = max(_finite(abs(a - b) / abs(b))
                   for a, b in zip(prog["loss"], ref["loss"]))
    raw = ref["grad_raw"]
    med_raw = statistics.median(raw.values())
    moving = {n for n, v in raw.items() if v >= STILL_LEAF_SHARE * med_raw}
    return {"loss_gap": loss_gap,
            "grad_gap": _worst_leaf(prog["grad"], ref["grad"]),
            "change_gap": _worst_leaf(prog["change"], ref["change"],
                                      keep=lambda n: n in moving)}

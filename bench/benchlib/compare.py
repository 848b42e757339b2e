"""The numbers that decide ``correct`` in a training cell, from the program's
readings and the reference's.

Both sides give, after the same first steps from the same weights and rows:

* ``losses``  -- each step's loss;
* ``grad``    -- per leaf, the norm of the first step's gradient as the
  optimizer holds it after that step (Adam's first moment for the tower's
  leaves; the square root of the rows' Adagrad accumulator growth for the
  embedding rows);
* ``change``  -- per leaf, the norm of the parameters' change after the
  steps, as the next step receives them.

Leaves are compared by the gap between the two norms, against the
reference's norm of that leaf or of the median leaf, whichever is larger.
Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of both comparisons: they move by round-off alone.
"""

from __future__ import annotations

import math

import numpy as np

NOUGHT = 1e-3  # share of the median leaf's gradient below which a leaf is rounding


def _median(xs) -> float:
    return float(np.median(np.asarray(list(xs), dtype=np.float64)))


def leaf_gaps(prog: dict, ref: dict, live: set) -> dict:
    """Per leaf: |norm(prog) - norm(ref)| / max(norm(ref), median norm(ref))."""
    med = _median(ref.values())
    out = {}
    for k in live:
        r, p = float(ref[k]), float(prog.get(k, math.nan))
        out[k] = abs(p - r) / max(r, med) if max(r, med) > 0 else math.inf
    return out


def live_leaves(ref_grad: dict) -> set:
    med = _median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v >= NOUGHT * med}


def worst(gaps: dict) -> float:
    vals = [v for v in gaps.values()]
    if not vals or any(not math.isfinite(v) for v in vals):
        return math.inf
    return max(vals)


def train_numbers(prog: dict, ref: dict) -> dict:
    """``loss_gap``, ``grad_gap`` and ``change_gap``; a reading that is not
    finite, or a leaf missing on the program's side, reads infinite."""
    live = live_leaves(ref["grad"])
    lp, lr = prog["losses"], ref["losses"]
    if len(lp) != len(lr):
        loss_gap = math.inf
    else:
        loss_gap = max((abs(a - b) for a, b in zip(lp, lr)), default=math.inf)
        if not math.isfinite(loss_gap):
            loss_gap = math.inf
    return {
        "loss_gap": loss_gap,
        "grad_gap": worst(leaf_gaps(prog["grad"], ref["grad"], live)),
        "change_gap": worst(leaf_gaps(prog["change"], ref["change"], live)),
    }

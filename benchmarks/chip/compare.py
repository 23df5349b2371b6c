"""The comparison that decides ``correct`` for a training cell.

Both sides give, for the first steps of one seed: the loss of each step, the
norm of each leaf's first gradient, and the norm of each leaf's change over
all the steps.  The program's gradient norms are read from its optimizer
state after one step (Adafactor's second moment at step 1 is g^2 + eps, so
sum(vr) * cols, or sum(v), is the gradient's squared norm); the reference's
come from its own gradients.  Three numbers are compared:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_norm_gap``: over leaves, the largest gap between the program's and
  the reference's gradient norm, over the larger of the reference's norm of
  that leaf and of the median leaf;
* ``update_norm_gap``: the same for the change of the weights, leaving out
  leaves whose reference gradient is under a thousandth of the median
  leaf's (their update under Adafactor is round-off amplified to full size).

Each is held to the limit in ``limits/<workload>.json``; a number that is
not finite fails.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict

NAMES = ("loss_gap", "grad_norm_gap", "update_norm_gap")
NEGLIGIBLE_GRAD = 1e-3


def _worst_leaf(got: Dict[str, float], want: Dict[str, float], leaves):
    if set(got) != set(want):
        return math.inf
    med = statistics.median(want[n] for n in leaves)
    gaps = [abs(got[n] - want[n]) / max(want[n], med, 1e-30) for n in leaves]
    return math.nan if any(map(math.isnan, gaps)) else max(gaps)


def readings(program: dict, reference: dict) -> Dict[str, float]:
    ref_g = reference["grad_norms"]
    med_g = statistics.median(ref_g.values())
    moved = [n for n in ref_g if ref_g[n] >= NEGLIGIBLE_GRAD * med_g]
    losses = list(zip(program["losses"], reference["losses"]))
    loss_gap = (max(abs(a - b) / abs(b) for a, b in losses)
                if len(program["losses"]) == len(reference["losses"])
                else math.inf)
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": _worst_leaf(program["grad_norms"], ref_g, list(ref_g)),
        "update_norm_gap": _worst_leaf(program["change_norms"],
                                       reference["change_norms"], moved),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(math.isfinite(values[k]) and values[k] <= limits[k]
               for k in NAMES)


def left_out(reference: dict):
    """Leaves left out of ``update_norm_gap`` by the gradient rule."""
    g = reference["grad_norms"]
    med = statistics.median(g.values())
    return sorted(n for n in g if g[n] < NEGLIGIBLE_GRAD * med)

"""The numbers that decide ``correct`` for a training cell.

The program's first steps (taken through the window's own call and
feed) against the plain reference's, from the same seed and batches:

  loss_gap    the widest |loss - ref| / |ref| over the compared steps
  grad_gap    the worst leaf's |norm(g) - norm(g_ref)| / max(norm(g_ref),
              median leaf norm), g the clipped gradient of step one as
              the optimizer took it (AdamW's first moment after one
              step, over 1 - beta1)
  update_gap  the same for the change of the parameters over the
              compared steps, leaving out leaves whose reference
              gradient is under a thousandth of the median leaf's: their
              update is Adam's normalisation of round-off

A cell's limits file (``bench/limits/<cell>.json``) names the numbers
that are compared, each with its limit; ``bench/calibrate.py`` reads
all three.
"""

from __future__ import annotations

import statistics

SMALL_GRAD = 1e-3     # leaves under this share of the median leaf's
                      # reference gradient are left out of update_gap


def _leaf_gap(prog: dict, ref: dict, keep=None) -> tuple:
    keys = [k for k in ref if keep is None or k in keep]
    if set(prog) != set(ref):
        missing = sorted(set(ref) ^ set(prog))[:4]
        return float("inf"), f"leaves differ: {missing}"
    med = statistics.median(ref[k] for k in keys)
    worst, where = 0.0, ""
    for k in keys:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst or gap != gap:
            worst, where = gap, k
    return worst, where


def gaps(prog: dict, ref: dict) -> dict:
    """{"loss_gap": (value, where), "grad_gap": ..., "update_gap": ...}"""
    lg = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])]
    if len(prog["loss"]) != len(ref["loss"]):
        lg = [float("inf")]
    worst = max(range(len(lg)), key=lambda i: lg[i])
    med = statistics.median(ref["grad_norms"].values())
    keep = {k for k, v in ref["grad_norms"].items() if v >= SMALL_GRAD * med}
    return {"loss_gap": (lg[worst], f"step {worst}"),
            "grad_gap": _leaf_gap(prog["grad_norms"], ref["grad_norms"]),
            "update_gap": _leaf_gap(prog["change_norms"],
                                    ref["change_norms"], keep)}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, lines): each number the limits name beside its limit.
    No limits, a limit with no number, or a number that is not finite,
    is not correct."""
    ok, lines = bool(limits), []
    for name, lim in limits.items():
        value, where = numbers.get(name, (float("nan"), "not computed"))
        good = value == value and value <= lim
        ok &= good
        lines.append((name, value, lim, where, good))
    return ok, lines

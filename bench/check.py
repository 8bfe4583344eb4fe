"""The comparison that decides ``correct`` for a training cell.

Both sides report, for the same weights and batches (``readings``):

* ``loss``: each step's loss;
* ``grad``: per leaf, the norm of the first step's gradient as the
  optimizer gets it, before clipping;
* ``change``: per leaf, the norm of the weights' change over the steps;
* ``data``: the entries in which the batches the program's source gave
  for those steps differ from the benchmark's stream (exact: limit 0).

A cell's file gives a limit for each number it compares.  A number with
no limit is read and printed, not compared: the cell found no limit that
lies between what sound runs and the control read (``PERF.md``).

Leaves are the weight kinds split per layer (``bench/weights.py``).  Each
number is a worst case over leaves (or steps), as a gap of norms:
``|prog - ref| / max(ref, median ref leaf)``, the median guarding leaves
whose norm is all but zero.  Leaves whose first reference gradient is
under a thousandth of the median leaf's would move by round-off alone
under Adam: they are left out of ``change``.
"""
from __future__ import annotations

import math

import numpy as np

#: a leaf's first reference gradient under this share of the median leaf's
#: is rounding, and its change is not compared
STILL_LEAF = 1e-3
NUMBERS = ("loss", "grad", "change", "data")


def _worst_leaf_gap(prog: dict, ref: dict, leaves) -> float:
    leaves = list(leaves)
    if set(leaves) - set(prog):
        return math.inf                      # a leaf the program lacks
    med = float(np.median([ref[k] for k in leaves]))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in leaves]
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def numbers(prog: dict, ref: dict) -> dict:
    """{number: value} for the program's readings against the reference's."""
    lp, lr = prog["loss"], ref["loss"]
    loss = max(abs(a - b) / abs(b) for a, b in zip(lp, lr))
    if len(lp) != len(lr) or not all(map(math.isfinite, lp)):
        loss = math.inf
    g_med = float(np.median(list(ref["grad"].values())))
    moving = [k for k, g in ref["grad"].items() if g >= STILL_LEAF * g_med]
    return {"loss": loss,
            "grad": _worst_leaf_gap(prog["grad"], ref["grad"], ref["grad"]),
            "change": _worst_leaf_gap(prog["change"], ref["change"], moving),
            "data": float(prog.get("data", 0))}


def verdict(nums: dict, limits: dict) -> bool:
    """Correct when every number that has a limit is finite and within
    it."""
    return all(math.isfinite(nums[k]) and nums[k] <= lim
               for k, lim in limits.items())


def report_lines(nums: dict, limits: dict) -> list[str]:
    """The numbers not compared, then each compared one with its limit."""
    return ([f"read {k} = {nums[k]!r} (not compared)"
             for k in NUMBERS if k not in limits]
            + [f"check {k} = {nums[k]!r} (limit {limits[k]!r})"
               for k in NUMBERS if k in limits])

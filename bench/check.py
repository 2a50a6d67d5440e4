"""The comparisons that decide ``correct``.

A training cell compares the program's first steps with the reference's
on the same rows:

* ``loss_gap``: the largest relative gap of a step's loss (read, not
  compared: no stand-in reads three times the program on it);
* ``grad_gap``: the median, over the leaves with each leaf stacked over
  the layers taken layer by layer, of the gap between the norm of the
  program's first gradient, as its optimizer took it, and the
  reference's, over the larger of that leaf's reference norm and the
  median leaf's.  The median and not the worst leaf: the worst is set
  by the ``D`` leaves, whose gradient is a sum that nearly cancels, so
  bfloat16 alone moves their norms by up to 1.5 % from seed to seed and
  the worst leaf swings fourteenfold; the median of some 400 leaves is
  steady, and a lower precision moves all of them;
* ``change_gap``: over the leaves, the largest gap between the norms of
  the program's and the reference's change of a leaf over the checked
  steps, over the larger of that leaf's reference norm and the median
  of the leaves that the reference moves.  A leaf whose AdamW step stays under half a bfloat16
  ulp is left unmoved by both and counts only if the program moves it;
  leaves whose reference gradient is under a thousandth of the median
  leaf's move by round-off alone and are left out.

Served bytes are compared exactly (``bytes_mismatch``, limit 0).
"""
from __future__ import annotations

import math
from statistics import median
from typing import Dict, Tuple


def train_gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses``, ``grad_norms``,
    ``layer_grad_norms`` and ``change_norms``."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"]))
    g_ref = ref["grad_norms"]
    g_med = median(g_ref.values())
    c_ref, c_prog = ref["change_norms"], prog["change_norms"]
    live = [n for n, g in g_ref.items() if g >= 1e-3 * g_med]
    moved = [n for n in live if c_ref[n] > 0]
    change_gap = math.inf
    if moved:
        floor = median(c_ref[n] for n in moved)
        change_gap = max(abs(c_prog[n] - c_ref[n]) / max(c_ref[n], floor)
                         for n in live if c_ref[n] > 0 or c_prog[n] > 0)
    l_ref, l_prog = ref["layer_grad_norms"], prog["layer_grad_norms"]
    l_med = median(l_ref.values())
    gaps = {"loss_gap": loss_gap,
            "grad_gap": median(abs(l_prog[n] - g) / max(g, l_med)
                               for n, g in l_ref.items()),
            "change_gap": change_gap}
    return {k: (v if math.isfinite(v) else math.inf) for k, v in gaps.items()}


def judge(readings: Dict[str, float],
          limits: Dict[str, float]) -> Tuple[bool, Dict[str, list]]:
    """Every reading against its limit; a missing reading fails."""
    table = {name: [readings.get(name, math.inf), limit]
             for name, limit in limits.items()}
    ok = all(v <= lim for v, lim in table.values())
    return ok, table

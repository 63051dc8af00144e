"""The numbers that decide ``correct`` for a training cell, and their limits.

Four numbers compare the program's first three steps with the plain
reference's, from the same weights and the same batches:

* ``loss``: the largest relative gap of a step's task loss;
* ``grad``: the first step's gradient, leaf by leaf, before the per-tensor
  normalisation (the optimizer gets it divided by its norm, which is 1 by
  construction, so the norm the controller records is read instead);
* ``change``: how far each leaf has moved after the three updates;
* ``switch``: how many of the tensors' (per layer) <WL, FL> pairs the
  window's compiled precision switch sets otherwise than the same switch
  on the XLA dispatch (``quant.use_pallas=false``) from the same state;
  exact, so its limit is 0.

``grad`` and ``change`` take the worst leaf of |norm_program - norm_reference|
over the larger of the reference's norm of that leaf and of the median leaf;
``grad_median`` takes the median leaf of the same gaps. The program and the
reference draw their stochastic rounding apart, so each leaf's gradient norm
differs by a few per cent at random, and the worst of a dozen leaves swings
from seed to seed; the median leaf is steady, and it separates a batch with
half its rows left out (about +40% on every leaf) from sound runs. Leaves
whose reference gradient is under a thousandth of the median leaf's
(nought to rounding) are left out of all three.
"""
from __future__ import annotations

import json
import math
import os
import statistics
from typing import Dict, List, Optional

NUMBERS = ("loss", "grad", "grad_median", "switch", "change")
NEGLIGIBLE = 1e-3


def _gaps(prog: Dict[str, float], ref: Dict[str, float], keep) -> list:
    med = statistics.median(ref[k] for k in keep)
    return [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keep]


def switch_gaps(words: Dict) -> Dict[str, int]:
    """{"switch": pairs the program sets otherwise than the XLA dispatch,
    "switch_moved": pairs the XLA dispatch moves from where they were};
    ``words``: {"program", "xla", "before"}, each {tensor: [[wl, fl], ...]}."""
    def differ(a, b):
        return sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))
    xla = words["xla"]
    return {"switch": sum(differ(words["program"].get(p, []), v)
                          for p, v in xla.items())
            + len(set(words["program"]) - set(xla)),
            "switch_moved": sum(differ(words["before"][p], v)
                                for p, v in xla.items())}


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog`` / ``ref``: {"losses": [...], "grad": {leaf: norm},
    "change": {leaf: norm}}; ``prog`` may hold the switch's words under
    "switch" (see ``switch_gaps``). NaN readings stand for non-finite
    values."""
    gmed = statistics.median(ref["grad"].values())
    keep = [k for k, v in ref["grad"].items() if v >= NEGLIGIBLE * gmed]
    sw = switch_gaps(prog["switch"]) if "switch" in prog else \
        {"switch": math.nan, "switch_moved": math.nan}
    vals = list(prog["losses"]) + list(prog["grad"].values()) + \
        list(prog["change"].values())
    if not all(math.isfinite(v) for v in vals):
        return {n: math.nan for n in NUMBERS} | {"leaves": len(keep),
                                                 **sw}
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"],
                                                     ref["losses"]))
    grad = _gaps(prog["grad"], ref["grad"], keep)
    return {"loss": loss, "grad": max(grad),
            "grad_median": statistics.median(grad),
            "change": max(_gaps(prog["change"], ref["change"], keep)),
            "leaves": len(keep), **sw}


def load_limits(root: str, workload: str) -> Optional[Dict[str, float]]:
    path = os.path.join(root, "bench", "limits", f"{workload}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)["limits"]


def judge(read: Dict[str, float], limits: Optional[Dict[str, float]]
          ) -> tuple[bool, List[dict]]:
    """(correct, [{name, value, limit}]) — a number with no limit, or one
    that is not finite, is not correct."""
    rows, ok = [], True
    for n in NUMBERS:
        lim = None if limits is None else limits.get(n)
        v = read[n]
        rows.append({"name": n, "value": v, "limit": lim})
        if lim is None or not math.isfinite(v) or v > lim:
            ok = False
    return ok, rows

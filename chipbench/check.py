"""The comparison that decides ``correct``.

The program's numbers and the reference's are compared number by number;
each number is a gap over steps, leaves or layer slices:

* ``loss``:   per step of the first chunk, |program - reference| over the
              reference's loss;
* ``grad``:   per leaf, the root-sum-square of the gradient norms the
              optimizer received over the chunk, read from LAMB's second
              moment (sqrt(sum v / (1 - b2))); the median leaf's gap, since
              the worst leaf's is rounding noise of one small leaf (RWKV's
              bonus, whose gradient sums products of bfloat16 activations
              over every position; PERF.md gives both readings);
* ``update``: per leaf, the norm of the parameters' change over the chunk;
              the median leaf's gap, since the worst leaf's is the sign
              noise of a zero-initialised LoRA matrix, which LAMB's first
              steps move by lr whatever its tiny gradient says;
* ``factor``: per layer slice and side, the norm of MKOR's inverse factor
              without its diagonal (what the rank-1 updates wrote into it).

Leaf and slice gaps are |program - reference| over the larger of the
reference's value and the median value, since some are all but zero.
Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of ``grad`` and
``update`` (the probes, which are never stepped, are among them).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

NUMBERS = ("loss", "grad", "update", "factor")
DEAD_LEAF = 1e-3


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], keys
               ) -> Dict[str, float]:
    keys = list(keys)
    med = float(np.median([ref[k] for k in keys]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys}
    return {k: (g if np.isfinite(g) else float("inf")) for k, g in gaps.items()}


def _worst(prog, ref, keys) -> Tuple[float, str]:
    gaps = _leaf_gaps(prog, ref, keys)
    name = max(gaps, key=gaps.get)
    return gaps[name], name


def _median(prog, ref, keys) -> Tuple[float, str]:
    gaps = _leaf_gaps(prog, ref, keys)
    value = float(np.median(list(gaps.values())))
    return value, f"median of {len(gaps)} leaves"


def gaps(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """{number: (gap, where)} for the four numbers, and the worst leaf's
    gradient and update gaps as readings beside them."""
    out = {}
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    rel = np.abs(lp - lr) / np.abs(lr)
    rel = np.where(np.isfinite(rel), rel, np.inf)
    out["loss"] = (float(rel.max()), f"step {int(rel.argmax())}")
    med = float(np.median(list(ref["grad"].values())))
    live = [k for k, v in ref["grad"].items() if v >= DEAD_LEAF * med]
    out["grad"] = _median(prog["grad"], ref["grad"], live)
    out["grad_worst_leaf"] = _worst(prog["grad"], ref["grad"], live)
    out["update"] = _median(prog["update"], ref["update"], live)
    out["update_worst_leaf"] = _worst(prog["update"], ref["update"], live)
    fp, fr = {}, {}
    for key, arr in ref["factor"].items():
        for i, v in enumerate(np.ravel(arr)):
            fr[f"{key}[{i}]"] = float(v)
            fp[f"{key}[{i}]"] = float(np.ravel(prog["factor"][key])[i])
    out["factor"] = _worst(fp, fr, fr)
    return out


def verdict(g: Dict[str, Tuple[float, str]], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict]]:
    """(correct, {number: {"value", "limit", "at"}}) over ``NUMBERS``."""
    rows = {k: {"value": g[k][0], "limit": limits[k], "at": g[k][1]}
            for k in NUMBERS}
    return all(r["value"] <= r["limit"] for r in rows.values()), rows

"""The numbers that decide ``correct``, each against its limit.

Both sides report, over the first rounds of one seed: each round's loss,
count of clients that sent a scalar and bytes on the wire, and per-leaf
norms of the parameters' change after round 1 (the first update the
server's step takes, times the learning rate) and after the last checked
round. Five numbers follow:

* ``loss_gap``: the largest relative gap of a round's loss;
* ``grad1_gap`` and ``change_gap``: for the first update and for the
  change after the last round, the worst leaf's gap between the two
  norms, ``|n_prog - n_ref| / max(n_ref, median leaf's n_ref)``;
* ``scalar_gap``: the clients whose decision differs, summed over rounds
  (the difference of each round's counts);
* ``wire_gap``: the largest relative gap of a round's wire bytes.

Leaves whose first update is under a thousandth of the median leaf's in
the reference move by round-off alone and are left out of both norms.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict

#: a leaf whose reference first update is under this share of the median
#: leaf's is left out of the norm gaps
NEGLIGIBLE = 1e-3


def counted_leaves(ref: dict):
    med = statistics.median(ref["d1"].values())
    return sorted(k for k, v in ref["d1"].items() if v >= NEGLIGIBLE * med)


def _norm_gap(prog: Dict[str, float], ref: Dict[str, float], leaves):
    med = statistics.median(ref[k] for k in leaves)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    leaves = counted_leaves(ref)
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    return {"loss_gap": loss,
            "grad1_gap": _norm_gap(prog["d1"], ref["d1"], leaves),
            "change_gap": _norm_gap(prog["dn"], ref["dn"], leaves),
            "scalar_gap": float(sum(abs(a - b) for a, b in
                                    zip(prog["scalars"], ref["scalars"]))),
            "wire_gap": max(abs(a - b) / b for a, b in
                            zip(prog["wire"], ref["wire"]))}


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every number is finite and within its limit."""
    return all(math.isfinite(nums.get(k, math.nan)) and nums[k] <= lim
               for k, lim in limits.items())

"""The numbers that decide ``correct``: the program's first rounds against
the plain reference's.

The warm-up drives the engine through two ``run`` calls, ``run(1)`` and
``run(3)``; the reference follows both.  Compared, per call:

* ``init_gap``    max |initial model - reference's initial model|; the
                  initial models come from the same key and must be equal.
* ``loss_gap``    worst |mean local loss - reference's| over the four rounds,
                  in nats.
* ``update_gap``  the cloud's first update (model after round 1 minus the
                  model before it), by the worst leaf: the gap between the
                  program's leaf norm and the reference's, over the larger
                  of the reference's leaf norm and its median leaf norm.
* ``change_gap``  the same for the change over the three rounds of
                  ``run(3)``.

A leaf whose reference update is under a thousandth of the median leaf's
is left out of both norm gaps (it moves by round-off alone).
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import numpy as np

CALLS = (1, 3)
NUMBERS = ("init_gap", "loss_gap", "update_gap", "change_gap")


def _leaves(tree) -> Dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64) for p, v in flat}


def _delta_norms(call: dict) -> Dict[str, float]:
    s, e = _leaves(call["start"]), _leaves(call["end"])
    return {k: float(np.linalg.norm(e[k] - s[k])) for k in s}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float], keep: List[str]) -> float:
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def numbers(prog_calls: List[dict], ref_calls: List[dict]) -> Dict[str, float]:
    """The four numbers for calls made as ``CALLS``; each call a dict with
    ``start``/``end`` model trees and per-round ``losses``."""
    p0, r0 = _leaves(prog_calls[0]["start"]), _leaves(ref_calls[0]["start"])
    if set(p0) != set(r0) or any(p0[k].shape != r0[k].shape for k in p0):
        raise ValueError("the program's model does not have the reference's leaves")
    init_gap = max(float(np.max(np.abs(p0[k] - r0[k]))) for k in p0)
    pl = [l for c in prog_calls for l in c["losses"]]
    rl = [l for c in ref_calls for l in c["losses"]]
    loss_gap = max(abs(a - b) if math.isfinite(a) else math.inf for a, b in zip(pl, rl))
    ref_first = _delta_norms(ref_calls[0])
    med = float(np.median(list(ref_first.values())))
    keep = [k for k, v in ref_first.items() if v >= 1e-3 * med]
    out = {"init_gap": init_gap, "loss_gap": loss_gap}
    for name, i in (("update_gap", 0), ("change_gap", 1)):
        prog = _delta_norms(prog_calls[i])
        if not all(math.isfinite(v) for v in prog.values()):
            out[name] = math.inf
        else:
            out[name] = worst_leaf_gap(prog, _delta_norms(ref_calls[i]), keep)
    return out


def judge(nums: Dict[str, float], limits: Dict[str, dict]) -> Dict[str, dict]:
    """Each compared number beside its limit; it passes at or under it.
    A number the cell's limits file leaves out is not compared."""
    return {k: {"value": nums[k], "limit": limits[k]["limit"],
                "ok": bool(nums[k] <= limits[k]["limit"])} for k in NUMBERS if k in limits}

"""Regenerate the golden CNN-trajectory pins (tests/golden/cnn_trajectory.json).

Runs the canonical heartbeat CNN scenario for 2 cloud rounds through the
three engine paths and records a sha256 over the final parameter bytes plus
the accuracy history.  ``tests/test_consistency.py`` asserts future code
reproduces these bytes exactly on the same jax version, so refactors cannot
silently drift the reference trajectories.

Usage: PYTHONPATH=src python tools/golden_trajectory.py
"""
from __future__ import annotations

import hashlib
import json
import os
import sys

import jax
import numpy as np


def params_hash(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def golden_runs():
    """The pinned runs: (name, SimResult) pairs on the canonical scenario."""
    from repro.federated import build_scenario

    sc = build_scenario("heartbeat", scale=0.02, seed=0, n_test_per_class=20)
    asn = sc.assign("eara-sca").lam
    kw = dict(cloud_rounds=2, seed=0, upp=1.0)
    runs = {
        "sync-device": sc.simulate(asn, engine="sync", **kw),
        "reference": sc.simulate(asn, engine="reference", **kw),
        "async": sc.simulate(
            asn, engine="async", quorum=0.75, staleness_decay=0.5, **kw
        ),
    }
    # streaming engine (ISSUE 9 satellite): the lazy heartbeat population
    # under cohort sampling — the same spec tests/test_stream.py checks for
    # stream==sync parity, pinned here so streaming refactors can't drift
    from repro.federated import CohortSpec

    ssc = build_scenario(
        "heartbeat", lazy=True, n_eus=120, n_edges=4, seed=3,
        n_test_per_class=20,
    )
    runs["stream"] = ssc.simulate(
        CohortSpec(size=24, seed=9), cloud_rounds=2, seed=0
    )
    return runs


def main() -> None:
    out = {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "scenario": "heartbeat scale=0.02 seed=0 eara-sca 2 cloud rounds",
        "runs": {},
    }
    for name, res in golden_runs().items():
        out["runs"][name] = {
            "params_sha256": params_hash(res.final_params),
            "accs": [round(m.test_acc, 10) for m in res.history],
        }
        print(f"{name}: {out['runs'][name]['params_sha256'][:16]}...  accs={out['runs'][name]['accs']}")
    path = os.path.join(os.path.dirname(__file__), "..", "tests", "golden", "cnn_trajectory.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {os.path.normpath(path)}")


if __name__ == "__main__":
    sys.exit(main())

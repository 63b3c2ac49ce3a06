"""Regenerate the mesh-trajectory pins (tests/golden/mesh_trajectory.json).

Runs the mesh-harness population (``make_population``: a micro 1-D CNN,
M=24 over 8 edges, T=2, 2 cloud rounds) through ``MeshSyncEngine`` on every
harness mesh size {1, 2, 4, 8} and records the accuracy history plus a
sha256 over the final parameter bytes per size.  ``tests/test_hfl_mesh.py``
asserts future code reproduces these exactly on the same jax version —
cross-mesh parity keeps accuracies identical across sizes, but the cloud
psum's float association makes each size's parameter BYTES its own pin.

Run as a script it forces 8 virtual devices before jax starts a backend:

    PYTHONPATH=src:. python tools/golden_mesh.py
"""
from __future__ import annotations

import json
import os
import sys

import jax
import numpy as np


def make_population(m: int, n_edges: int):
    """M micro-CNN clients with small imbalanced shards, round-robin over
    ``n_edges`` edges.  Returns ``(clients, assignment, test, program)``;
    shared by the mesh tests, these pins and ``benchmarks/distributed_bench.py``."""
    from repro.data.partition import split_dataset_by_counts
    from repro.data.synthetic_health import heartbeat_like
    from repro.federated.client import FLClient
    from repro.federated.programs import CNNProgram
    from repro.models.cnn1d import CNNConfig

    cfg = CNNConfig(in_channels=1, n_classes=5, seq_len=64, c1=8, c2=8, hidden=16)
    program = CNNProgram(cfg)
    rng = np.random.default_rng(0)
    counts = rng.integers(1, 3, (m, cfg.n_classes))
    train = heartbeat_like(rng, counts.sum(axis=0))
    train.x = train.x[:, : cfg.seq_len, : cfg.in_channels]
    shards = split_dataset_by_counts(rng, train, counts)
    test = heartbeat_like(rng, np.full(cfg.n_classes, 10))
    test.x = test.x[:, : cfg.seq_len, : cfg.in_channels]
    clients = [FLClient(i, shards[i], program) for i in range(m)]
    assignment = np.zeros((m, n_edges))
    assignment[np.arange(m), np.arange(m) % n_edges] = 1.0
    return clients, assignment, test, program


def mesh_runs(ks=(1, 2, 4, 8)):
    from repro.core.hfl import HFLSchedule
    from repro.engine.mesh_sim import MeshSyncEngine

    clients, assignment, test, program = make_population(24, 8)
    out = {}
    for k in ks:
        if k > jax.device_count():
            continue
        eng = MeshSyncEngine(
            clients, assignment, program, test,
            schedule=HFLSchedule(2, 2), seed=0, mesh=k,
        )
        out[f"k{k}"] = eng.run(2, eval_every=1)
    return out


def main() -> None:
    from tools.golden_trajectory import params_hash

    out = {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "scenario": "make_population micro-CNN m=24 e=8 T=2 seed=0, 2 cloud rounds",
        "runs": {},
    }
    for name, res in mesh_runs().items():
        out["runs"][name] = {
            "params_sha256": params_hash(res.final_params),
            "accs": [round(m.test_acc, 10) for m in res.history],
        }
        print(f"{name}: {out['runs'][name]['params_sha256'][:16]}...  "
              f"accs={out['runs'][name]['accs']}")
    path = os.path.join(
        os.path.dirname(__file__), "..", "tests", "golden", "mesh_trajectory.json"
    )
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {os.path.normpath(path)}")


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
    )
    sys.exit(main())

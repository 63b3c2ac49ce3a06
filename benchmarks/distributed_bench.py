"""Mesh engine comm accounting: the paper's 1/T claim in compiled HLO.

Runs ``MeshSyncEngine`` over {1, 2, 4, 8} virtual devices (a CPU-only
subprocess with ``--xla_force_host_platform_device_count=8``) and reports, per mesh size,
trajectory parity against the single-device ``BatchedSyncEngine`` and the
``MeshCommLedger`` HLO collective-byte readings; then sweeps T
(edge rounds per cloud round) at the full mesh and checks the structural
claim — cross-edge collective bytes per EDGE round scale as payload/T while
the edge programs themselves stay collective-free.  ``CommAccountant``'s
simulated bits ride along so the measured and modeled ledgers sit side by
side in ``BENCH_distributed.json``.

Caveat (docs/BENCHMARKS.md): virtual CPU devices share one thread pool, so
nothing here is a wall-clock speedup measurement — the deliverable is
topology correctness + accounting.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import QUICK, dump_json, emit, mark

_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax
from tools.golden_mesh import make_population
from repro.core.hfl import HFLSchedule
from repro.engine import BatchedSyncEngine
from repro.engine.mesh_sim import MeshSyncEngine

KS = %(ks)s
TS = %(ts)s
ROUNDS = 2
clients, assignment, test, program = make_population(24, 8)
flat = lambda p: np.concatenate(
    [np.ravel(np.asarray(l)) for l in jax.tree_util.tree_leaves(p)]
)

def run_base(t):
    eng = BatchedSyncEngine(clients, assignment, program, test,
                            schedule=HFLSchedule(2, t), seed=0)
    return eng.run(ROUNDS, eval_every=1)

def run_mesh(k, t):
    eng = MeshSyncEngine(clients, assignment, program, test,
                         schedule=HFLSchedule(2, t), seed=0, mesh=k)
    return eng.run(ROUNDS, eval_every=1), eng.comm_report()

base = {t: run_base(t) for t in sorted(set(TS) | {2})}
out = {"devices": jax.device_count(), "parity": {}, "t_sweep": {}}
for k in KS:
    rm, rep = run_mesh(k, 2)
    rb = base[2]
    out["parity"][str(k)] = {
        "param_diff": float(np.max(np.abs(flat(rb.final_params) - flat(rm.final_params)))),
        "acc_diff": float(max(abs(a.test_acc - b.test_acc)
                              for a, b in zip(rb.history, rm.history))),
        "xe_per_cloud": rep["cross_edge_bytes_per_cloud_round"],
        "payload": rep["payload_bytes"],
    }
kmax = max(KS)
for t in TS:
    rm, rep = run_mesh(kmax, t)
    rb = base[t]
    edge_xe = sum(v["cross_edge_bytes_total"]
                  for kk, v in rep["programs"].items() if kk != "cloud_reduce")
    out["t_sweep"][str(t)] = {
        "param_diff": float(np.max(np.abs(flat(rb.final_params) - flat(rm.final_params)))),
        "xe_per_cloud": rep["cross_edge_bytes_per_cloud_round"],
        "xe_per_edge_round": rep["cross_edge_bytes_per_edge_round"],
        "edge_program_xe": edge_xe,
        "payload": rep["payload_bytes"],
        "edge_rounds": rep["edge_rounds"],
        "cloud_syncs": rep["cloud_syncs"],
        "simulated_cloud_bits": rep["simulated"]["cloud_bits"],
        "simulated_eu_bits": rep["simulated"]["eu_up_bits"]
        + rep["simulated"]["eu_down_bits"],
    }
print(json.dumps(out))
"""


def main() -> None:
    start = mark()
    _run()
    dump_json("BENCH_distributed.json", start)


def _run() -> None:
    root = os.path.join(os.path.dirname(__file__), "..")
    src = os.path.join(root, "src")
    ks, ts = ((1, 8), (1, 4)) if QUICK else ((1, 2, 4, 8), (1, 2, 4))
    # the child is a CPU accounting tool on virtual devices: it never
    # contends with a parent that holds an accelerator
    env = dict(os.environ, PYTHONPATH=os.pathsep.join((src, root)), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    code = _CODE % {"ks": repr(tuple(ks)), "ts": repr(tuple(ts))}
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1500)
    if res.returncode != 0:
        emit("distributed_mesh", 0.0,
             "FAILED: " + res.stderr.strip().splitlines()[-1][:120])
        raise RuntimeError(f"distributed_mesh child failed:\n{res.stderr[-3000:]}")
    data = json.loads(res.stdout.strip().splitlines()[-1])
    bad = []
    for k, row in data["parity"].items():
        ok = row["param_diff"] <= 1e-6 and row["acc_diff"] <= 1e-6
        if not ok:
            bad.append(f"parity k={k}")
        emit(f"mesh_parity_k{k}", 0.0,
             f"max|dparam|={row['param_diff']:.2e} acc_diff={row['acc_diff']:.1e} "
             f"xe/cloud={row['xe_per_cloud']:.3e} B", **row)
    for t, row in data["t_sweep"].items():
        expect = row["payload"] / int(t)  # cross-edge bytes amortize 1/T
        rel = abs(row["xe_per_edge_round"] - expect) / max(expect, 1.0)
        if row["edge_program_xe"] != 0.0 or rel > 0.05:
            bad.append(f"1/T t={t}")
        emit(f"mesh_cross_edge_T{t}", 0.0,
             f"xe/edge_round={row['xe_per_edge_round']:.3e} B "
             f"(payload/T={expect:.3e}) edge_programs={row['edge_program_xe']:.0f} B "
             f"sim_cloud={row['simulated_cloud_bits']:.3e} bits", **row)
    if bad:
        emit("distributed_mesh", 0.0, "FAILED: " + ", ".join(bad))
        raise RuntimeError("distributed_mesh checks failed: " + ", ".join(bad))


if __name__ == "__main__":
    main()

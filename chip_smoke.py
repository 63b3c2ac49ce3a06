"""Chip smoke test: the paper's hierarchical federation, once, on a TPU.

    python chip_smoke.py            # one chip: kernel phase + federation phase
    python chip_smoke.py --mesh 4   # four chips: MeshSyncEngine vs the one-chip
                                    # device pipeline, and nothing else

Runs in one process through the public entry points, with no fallback: it
exits non-zero, printing no result line, when JAX sees no TPU or when any
phase fails.  Earlier lines name the device and report each phase; the
per-round wall times printed there are smoke timings, not metrics.  The
last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# sync vs reference test accuracy on the 1,500-sample heartbeat test set,
# checked after cloud round 1 and after the last.  The two engines run the
# same semantics in different float32 summation orders (batched cohort GEMMs
# vs per-client convs), and over ~1,200 Adam steps their trajectories part
# even on the CPU (0.0093 apart at round 3).  On a TPU v5e, whose default
# matmul precision rounds f32 operands to bf16, they part by 0.0327 at round
# 2 (0.0040 under "highest" precision).  Round 1 is unsaturated and read
# before that drift compounds: 0.0033 apart on a TPU v5e, while an
# unweighted cloud mean moves it by 0.128.  The last round saturates near
# 1.0 and bounds drift only.
ACC_TOL = 0.02
# --mesh: MeshSyncEngine and the one-chip device pipeline do the same
# per-client math, sharded or not, and agree to <= 5e-6 on the CPU.  At the
# chip's default matmul precision Adam turns their shape-dependent bf16
# rounding into parameter drift (TPU v5e: 1.6e-3 max, 6.1e-5 median) that an
# unweighted cloud mean (2.9e-3 max, 1.4e-4 median) cannot be told from.  So
# the pair runs twice: at default precision, the path users run, checking
# accuracy every round; then inside a scoped
# ``jax.default_matmul_precision("highest")`` (nothing global is set), where
# the final parameters must agree to MESH_PARAM_TOL (TPU v5e: sound 3.0e-5,
# the unweighted-mean mutant 2.3e-3).
MESH_PARAM_TOL = 1e-4
MESH_ACC_TOL = 0.01

F32_EPS = float(np.finfo(np.float32).eps)


def say(msg: str) -> None:
    print(msg, flush=True)


def _check_fedavg(name: str, got, want, n_terms: int, scale: float) -> None:
    """float32 rounding bound of an n-term weighted sum: n * eps * max|x|."""
    err = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
    bound = n_terms * F32_EPS * scale
    say(f"kernel phase: {name} max|err| vs float64 FedAvg = {err:.3e} "
        f"(float32 bound {bound:.3e})")
    if not err <= bound:
        raise AssertionError(f"{name}: error {err:.3e} exceeds float32 rounding {bound:.3e}")


def _assert_kernel(name: str, jitted, *args, **kw) -> None:
    text = jitted.lower(*args, **kw).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{name}: compiled program holds no tpu_custom_call")
    say(f"{name} compiled to a Mosaic kernel (tpu_custom_call)")


def kernel_phase() -> None:
    """Edge and cloud FedAvg at the paper's shape (18 EUs, 5 edges, the
    heartbeat CNN's flat width) against a float64 numpy reference."""
    from repro.engine.flatten import FlatPack, flat_mean, flat_segment_mean
    from repro.federated.programs import CNNProgram
    from repro.kernels import ops
    from repro.models.cnn1d import HEARTBEAT_CNN

    d = FlatPack(CNNProgram(HEARTBEAT_CNN).init(jax.random.PRNGKey(0))).dim
    n, e = 18, 5
    rng = np.random.default_rng(0)
    x = (0.1 * rng.standard_normal((n, d))).astype(np.float32)
    seg = (np.arange(n) % e).astype(np.int32)
    w = rng.integers(20, 400, n).astype(np.float32)  # shard sizes
    x64, w64 = x.astype(np.float64), w.astype(np.float64)
    scale = float(np.abs(x).max())
    say(f"kernel phase: N={n} E={e} D={d}")

    xd, segd, wd = jnp.asarray(x), jnp.asarray(seg), jnp.asarray(w)
    want = np.stack([w64[seg == j] @ x64[seg == j] / w64[seg == j].sum() for j in range(e)])
    _check_fedavg("flat_segment_mean (edge FedAvg)",
                  flat_segment_mean(xd, segd, wd, e), want, n, scale)
    _assert_kernel("kernel phase: hier_segment_aggregate", ops.hier_segment_aggregate, xd, segd, wd, e)

    _check_fedavg("flat_mean N=18 (kernel path)", flat_mean(xd, wd),
                  w64 @ x64 / w64.sum(), n, scale)
    _assert_kernel("kernel phase: hier_aggregate", ops.hier_aggregate, xd, wd)

    # the cloud reduction over E=5 edge models takes the small-N contraction
    _check_fedavg("flat_mean N=5 (cloud reduce)", flat_mean(xd[:e], wd[:e]),
                  w64[:e] @ x64[:e] / w64[:e].sum(), e, scale)


def _assert_edge_fedavg_kernel(sc, lam) -> None:
    """The sync engine's edge FedAvg is one jitted program
    (``segment_agg_keep``) that calls the segment kernel inside it, so the
    kernel's own jit cache stays empty.  Proof that the kernel ran: that
    program compiled during the run, and at the run's shapes it holds the
    Mosaic kernel."""
    from repro.engine.flatten import FlatPack
    from repro.telemetry import jit_cache_sizes, registered_jits

    n_compiled = jit_cache_sizes()["segment_agg_keep"]
    say(f"federation phase: segment_agg_keep compiled programs = {n_compiled}")
    if n_compiled < 1:
        raise AssertionError("the sync engine's edge FedAvg program never ran")
    pairs, e = int((np.asarray(lam) > 0).sum()), sc.n_edges
    d = FlatPack(sc.program.init(jax.random.PRNGKey(0))).dim
    f32 = jnp.float32
    _assert_kernel(
        "federation phase: segment_agg_keep (edge FedAvg)", registered_jits()["segment_agg_keep"],
        jax.ShapeDtypeStruct((pairs, d), f32), jax.ShapeDtypeStruct((pairs,), jnp.int32),
        jax.ShapeDtypeStruct((pairs,), f32), jax.ShapeDtypeStruct((e,), jnp.bool_),
        jax.ShapeDtypeStruct((e, d), f32), e, "pallas",
    )


def _timed(label: str, run):
    """Run one simulation; print its per-round smoke timings; require
    finite losses and accuracies."""
    t0 = time.perf_counter()
    res = run()
    for m in res.history:
        say(f"smoke timing (not a metric): {label} round {m.cloud_round} "
            f"wall_seconds={m.wall_seconds:.3f} acc={m.test_acc:.4f} "
            f"loss={m.mean_local_loss:.4f}")
    say(f"smoke timing (not a metric): {label} {time.perf_counter() - t0:.1f}s "
        "including compiles")
    if not all(math.isfinite(m.mean_local_loss) and math.isfinite(m.test_acc)
               for m in res.history):
        raise AssertionError(f"{label}: non-finite loss or accuracy")
    return res


def _flat(params) -> np.ndarray:
    return np.concatenate([np.ravel(np.asarray(l, np.float64)) for l in jax.tree.leaves(params)])


def _compare(label: str, a, b, acc_tol: float, rounds=None, param_tol=None) -> None:
    """Test-accuracy gap per cloud round, checked at the history indices
    ``rounds`` (every round when None), and the final parameters' drift,
    checked against ``param_tol`` when one is given."""
    gaps = [abs(x.test_acc - y.test_acc) for x, y in zip(a.history, b.history)]
    checked = gaps if rounds is None else [gaps[i] for i in rounds]
    d = np.abs(_flat(a.final_params) - _flat(b.final_params))
    say(f"{label}: |dacc| by round " + " ".join(f"{g:.4f}" for g in gaps)
        + f" (tolerance {acc_tol} at rounds "
        + ("all" if rounds is None else ",".join(str(a.history[i].cloud_round) for i in rounds))
        + f"); final params max|d|={d.max():.3e} median|d|={np.median(d):.3e} "
        f"mean|d|={d.mean():.3e} (tolerance on max: {param_tol})")
    if max(checked) > acc_tol:
        raise AssertionError(f"{label}: accuracies disagree by {max(checked):.4f}")
    if param_tol is not None and not d.max() <= param_tol:
        raise AssertionError(f"{label}: parameters disagree by {d.max():.3e}")


def federation_phase() -> None:
    """The paper's heartbeat federation at full width through
    ``Scenario.simulate``: the sync engine's device pipeline (edge FedAvg in
    the segment kernel), then the reference engine for comparison."""
    from repro.core.hfl import HFLSchedule
    from repro.federated import build_scenario

    sc = build_scenario("heartbeat", scale=1.0, seed=0)
    lam = sc.assign("eara-sca").lam
    say(f"federation phase: {len(sc.clients)} EUs, {sc.n_edges} edges, HEARTBEAT_CNN, "
        "EARA-SCA, T=4, 3 cloud rounds")
    kw = dict(cloud_rounds=3, schedule=HFLSchedule(1, 4), seed=0)
    res = {}
    for engine in ("sync", "reference"):
        res[engine] = _timed(engine, lambda: sc.simulate(lam, engine=engine, **kw))
        if engine == "sync":
            _assert_edge_fedavg_kernel(sc, lam)
            if res["sync"].final_accuracy() < 0.9:
                raise AssertionError(
                    f"sync final accuracy {res['sync'].final_accuracy():.4f} < 0.9")
    _compare("federation phase: sync vs reference", res["sync"], res["reference"],
             ACC_TOL, rounds=(0, -1))


def _mesh_population(m: int = 32, n_edges: int = 8, seed: int = 0):
    """Round-robin SCA population of heartbeat-CNN EUs with imbalanced
    shards, shaped like ``benchmarks/distributed_bench.py``'s."""
    from repro.data.partition import split_dataset_by_counts
    from repro.data.synthetic_health import heartbeat_like
    from repro.federated.client import FLClient
    from repro.federated.programs import CNNProgram
    from repro.models.cnn1d import HEARTBEAT_CNN

    rng = np.random.default_rng(seed)
    k = HEARTBEAT_CNN.n_classes
    counts = rng.integers(5, 40, (m, k))
    shards = split_dataset_by_counts(rng, heartbeat_like(rng, counts.sum(axis=0)), counts)
    test = heartbeat_like(rng, np.full(k, 100))
    program = CNNProgram(HEARTBEAT_CNN)
    clients = [FLClient(i, shards[i], program) for i in range(m)]
    assignment = np.zeros((m, n_edges))
    assignment[np.arange(m), np.arange(m) % n_edges] = 1.0
    return clients, assignment, test, program


def mesh_phase(k: int) -> None:
    """``MeshSyncEngine`` over k chips against ``BatchedSyncEngine``'s device
    pipeline on one chip, same population, schedule and seed."""
    from repro.core.hfl import HFLSchedule
    from repro.engine import BatchedSyncEngine
    from repro.engine.mesh_sim import MeshSyncEngine

    if jax.device_count() < k:
        raise AssertionError(f"--mesh {k} needs {k} devices, JAX sees {jax.device_count()}")
    clients, asn, test, program = _mesh_population()
    kw = dict(schedule=HFLSchedule(1, 2), seed=0)
    rounds = 3
    say(f"mesh phase: {len(clients)} EUs, {asn.shape[1]} edges, HEARTBEAT_CNN, "
        f"T=2, {rounds} cloud rounds, mesh={k}")
    for precision in ("default", "highest"):
        runs = {}
        with jax.default_matmul_precision(None if precision == "default" else precision):
            for name in ("device", "mesh"):
                if name == "mesh":
                    eng = MeshSyncEngine(clients, asn, program, test, mesh=k, **kw)
                    if eng.n_devices != k:
                        raise AssertionError(f"mesh engine runs on {eng.n_devices} devices, not {k}")
                else:
                    eng = BatchedSyncEngine(clients, asn, program, test, **kw)
                runs[name] = _timed(f"{name} ({precision} precision)",
                                    lambda: eng.run(rounds, eval_every=1))
        if precision == "default":
            rep = eng.comm_report()
            say(f"mesh phase: n_devices={eng.n_devices} comm_report "
                f"cross_edge_bytes_per_cloud_round={rep['cross_edge_bytes_per_cloud_round']:.0f} "
                f"cross_edge_bytes_per_edge_round={rep['cross_edge_bytes_per_edge_round']:.0f} "
                f"payload_bytes={rep['payload_bytes']}")
        _compare(f"mesh phase ({precision} precision): device vs mesh", runs["device"],
                 runs["mesh"], MESH_ACC_TOL,
                 param_tol=MESH_PARAM_TOL if precision == "highest" else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0, metavar="K",
                    help="run only the K-chip mesh phase and its one-chip comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU visible (platform {dev.platform!r})", file=sys.stderr)
        return 1

    from repro.utils.compile_cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    if args.mesh:
        mesh_phase(args.mesh)
    else:
        kernel_phase()
        federation_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Engine throughput benchmark: clients/sec for the simulation paths.

Compares, at M in {18, 128, 512, 2048} EUs on one cloud round:

  * ``sync-loop``    — the sequential reference ``HFLSimulation`` (one jitted
                       ``_local_epoch`` dispatch per client); skipped at
                       M >= 2048 in quick mode, where its per-client
                       dispatch loop no longer finishes in reasonable time;
  * ``batched-sync`` — ``BatchedSyncEngine(pipeline="host")``: the PR 1
                       engine (vmapped cohorts, host-major per-edge
                       aggregation loop);
  * ``device-sync``  — ``BatchedSyncEngine(pipeline="device")``: the PR 2
                       device-resident round pipeline (shard store, fused
                       segment aggregation, (E, D) edge matrix);
  * ``async``        — ``AsyncHFLEngine`` with a 75% quorum.

``--model`` (or ``main(model=...)``) picks the client program: ``cnn``
(default), ``mlp``, ``lm``, ``moe``, ``mamba``, ``rwkv``, or ``mix`` — the
engines are model-agnostic, so the same four paths run any registered
``ClientProgram``; every emitted mark records the program name.  The
sequence models (lm/moe/mamba/rwkv) share one token-shard population
layout, so their rows compare workloads on identical data.  ``mix`` is the
heterogeneous-MODEL population (half micro-CNN, half micro-MLP EUs with a
per-edge public shard): it times the distillation aggregation layer —
per-group cohorts, per-group segment FedAvg, and the per-cloud-round KD
fuse — against the ``HeteroHFLSimulation`` reference loop.  The full suite
(``benchmarks.run``) runs the CNN sizes plus one MLP scale point so CI
tracks at least one non-CNN trajectory; single-model sweeps land in
``BENCH_engine_<model>.json``.

The CNN workload is the dispatch-bound IoT regime the engine exists for: a
micro 1-D CNN (seq 64, ~4k params) and small local shards, so per-client
Python/dispatch overhead — what the engine eliminates — dominates the
reference loop.  With the paper-size model (25k params, seq 187) the same
comparison is compute-bound on a small CPU and the gap narrows; rerun with
``BENCH_MODEL=paper`` to see that regime.

Acceptance targets: batched-sync >= 5x sync-loop at M = 512 (ISSUE 1);
device-sync >= 2x batched-sync at M = 512 (ISSUE 2).  Results land in
``BENCH_engine.json``.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

import numpy as np

from benchmarks.common import QUICK, dump_json, emit, mark, span_stats
from repro.telemetry.trace import Tracer
from repro.core.hfl import HFLSchedule
from repro.data.lm_stream import TokenStream
from repro.data.synthetic_health import Dataset, heartbeat_like
from repro.data.partition import split_dataset_by_counts
from repro.engine import AsyncHFLEngine, BatchedSyncEngine, DistillSpec
from repro.federated.client import FLClient
from repro.federated.programs import (
    SEQUENCE_PROGRAMS,
    CNNProgram,
    LMProgram,
    MambaProgram,
    MLPProgram,
    MoEProgram,
    tiny_lm_config,
    tiny_mamba_config,
    tiny_moe_config,
    tiny_rwkv_config,
    RWKVProgram,
)
from repro.federated.simulation import HeteroHFLSimulation, HFLSimulation
from repro.models.cnn1d import CNNConfig, HEARTBEAT_CNN

MICRO_CNN = CNNConfig(in_channels=1, n_classes=5, seq_len=64, c1=8, c2=8, hidden=16)
CFG = HEARTBEAT_CNN if os.environ.get("BENCH_MODEL", "") == "paper" else MICRO_CNN

LM_SEQ, LM_VOCAB, LM_TOPICS = 16, 64, 4


def _program(model: str):
    seq_kw = dict(seq_len=LM_SEQ, n_topics=LM_TOPICS)
    if model == "cnn":
        return CNNProgram(CFG)
    if model == "mlp":  # micro MLP on the same micro-CNN shards
        return MLPProgram(feat=(CFG.seq_len, CFG.in_channels), classes=CFG.n_classes,
                          hidden=16)
    if model == "lm":  # micro causal transformer on token shards
        cfg = tiny_lm_config(vocab_size=LM_VOCAB, seq_len=LM_SEQ, d_model=16,
                             n_layers=2, n_heads=2, d_ff=32)
        return LMProgram(cfg=cfg, **seq_kw)
    if model == "moe":  # micro top-k-routed MoE LM, dense-gated dispatch
        cfg = tiny_moe_config(vocab_size=LM_VOCAB, seq_len=LM_SEQ, d_model=16,
                              n_layers=2, n_heads=2, d_ff=16, n_experts=4, top_k=2)
        return MoEProgram(cfg=cfg, **seq_kw)
    if model == "mamba":  # micro hybrid attn+mamba LM
        cfg = tiny_mamba_config(vocab_size=LM_VOCAB, seq_len=LM_SEQ, d_model=16,
                                n_layers=2, n_heads=2, d_ff=32, d_state=4)
        return MambaProgram(cfg=cfg, **seq_kw)
    if model == "rwkv":  # micro RWKV-6 LM
        cfg = tiny_rwkv_config(vocab_size=LM_VOCAB, seq_len=LM_SEQ, d_model=16,
                               n_layers=2, d_ff=32, head_size=8)
        return RWKVProgram(cfg=cfg, **seq_kw)
    raise ValueError(f"unknown model {model!r} (cnn | mlp | {' | '.join(SEQUENCE_PROGRAMS)})")


def _make_population(m: int, n_edges: int, seed: int = 0, model: str = "cnn"):
    """M clients with small imbalanced shards + round-robin edge assignment.

    Returns ``(clients, assignment, test, latency, program, public)``;
    ``public`` (one small Dataset per edge) is None except for ``mix``, the
    heterogeneous-model population (first half micro-CNN EUs, second half
    micro-MLP) whose engines fuse by distillation on it.
    """
    rng = np.random.default_rng(seed)
    public = None
    program = _program("cnn" if model == "mix" else model)
    if model in SEQUENCE_PROGRAMS:
        counts = rng.integers(1, 3, (m, LM_TOPICS))
        streams = [TokenStream(LM_VOCAB, seed=seed, topic=t) for t in range(LM_TOPICS)]
        shards = []
        for i in range(m):
            xs = [streams[t].batch(int(counts[i, t]), LM_SEQ) for t in range(LM_TOPICS)]
            ys = [np.full((int(counts[i, t]),), t, np.int32) for t in range(LM_TOPICS)]
            shards.append(
                Dataset(np.concatenate(xs, 0), np.concatenate(ys, 0), LM_TOPICS)
            )
        test = Dataset(
            np.concatenate([s.batch(10, LM_SEQ) for s in streams], 0),
            np.concatenate([np.full((10,), t, np.int32) for t in range(LM_TOPICS)], 0),
            LM_TOPICS,
        )
    else:
        k = CFG.n_classes
        counts = rng.integers(1, 3, (m, k))
        train = heartbeat_like(rng, counts.sum(axis=0))
        train.x = train.x[:, : CFG.seq_len, : CFG.in_channels]
        shards = split_dataset_by_counts(rng, train, counts)
        test = heartbeat_like(rng, np.full(k, 10))
        test.x = test.x[:, : CFG.seq_len, : CFG.in_channels]
        if model == "mix":  # per-edge public pools for the distillation fuse
            public = []
            for _ in range(n_edges):
                pub = heartbeat_like(rng, np.full(k, 3))
                pub.x = pub.x[:, : CFG.seq_len, : CFG.in_channels]
                public.append(pub)
    per_eu = [program] * m
    if model == "mix":  # capability skew: strong half CNN, weak half MLP
        mlp = _program("mlp")
        per_eu = [program if i < m // 2 else mlp for i in range(m)]
    clients = [FLClient(i, shards[i], per_eu[i]) for i in range(m)]
    assignment = np.zeros((m, n_edges))
    assignment[np.arange(m), np.arange(m) % n_edges] = 1.0
    latency = rng.uniform(0.01, 0.2, (m, n_edges))
    return clients, assignment, test, latency, program, public


def _time_interleaved(
    makers: Dict[str, object], repeats: int = 3
) -> Dict[str, Dict[str, float]]:
    """One-cloud-round wall time per contender (telemetry tracer spans, one
    per timed run); first (warmup) run compiles.  The timed runs are
    INTERLEAVED round-robin so a load spike on a shared box hits every
    contender, not whichever happened to be running — consecutive per-engine
    timing made the speedup ratios a lottery under noisy-neighbor variance.
    Returns per-contender ``{"best_us", "mean_us", "std_us", "repeats"}``."""
    tracer = Tracer()
    for make_sim in makers.values():
        make_sim().run(1, eval_every=1)
    for _ in range(repeats):
        for k, make_sim in makers.items():
            sim = make_sim()
            with tracer.span(k):
                sim.run(1, eval_every=1)
    return {k: span_stats(tracer.durations(k)) for k in makers}


def bench_scale(m: int, n_edges: int, model: str = "cnn") -> Dict[str, Optional[float]]:
    clients, assignment, test, latency, program, public = _make_population(
        m, n_edges, model=model
    )
    mk = dict(program=program, test=test, schedule=HFLSchedule(1, 1), seed=0)
    kd = dict(public_shards=public, distill=DistillSpec()) if public else {}
    tag = "" if model == "cnn" else f"{model}_"  # cnn names stay PR-comparable

    makers = {
        "host": lambda: BatchedSyncEngine(
            clients, assignment, pipeline="host", **kd, **mk
        ),
        "device": lambda: BatchedSyncEngine(
            clients, assignment, pipeline="device", **kd, **mk
        ),
        "async": lambda: AsyncHFLEngine(
            clients, assignment, latency=latency, quorum=0.75, **kd, **mk
        ),
    }
    # the sequential per-client loop is the baseline everywhere it is
    # feasible; at M >= 2048 its dispatch loop takes minutes per round, so
    # quick mode (CI) skips it and anchors ratios on the PR 1 engine
    if m < 2048 or not QUICK:
        if model == "mix":
            makers["loop"] = lambda: HeteroHFLSimulation(
                clients, assignment, test, schedule=HFLSchedule(1, 1), seed=0,
                public=public, distill=DistillSpec(),
            )
        else:
            makers["loop"] = lambda: HFLSimulation(clients, assignment, **mk)
    t = _time_interleaved(makers)

    def best_s(key):
        return t[key]["best_us"] * 1e-6

    def stat_kw(key):
        return dict(mean_us=t[key]["mean_us"], std_us=t[key]["std_us"],
                    repeats=t[key]["repeats"])

    t_ref = best_s("loop") if "loop" in t else None
    t_host, t_dev, t_async = best_s("host"), best_s("device"), best_s("async")

    prog = f"program={'mix(cnn+mlp)' if model == 'mix' else program.name}"
    if t_ref is not None:
        emit(f"engine_sync_loop_{tag}m{m}", t_ref * 1e6,
             f"{m / t_ref:.1f} clients/sec {prog}", **stat_kw("loop"))
        emit(f"engine_batched_sync_{tag}m{m}", t_host * 1e6,
             f"{m / t_host:.1f} clients/sec ({t_ref / t_host:.1f}x vs loop) {prog}",
             **stat_kw("host"))
    else:
        emit(f"engine_sync_loop_{tag}m{m}", 0.0,
             f"skipped in quick mode (infeasible) {prog}")
        emit(f"engine_batched_sync_{tag}m{m}", t_host * 1e6,
             f"{m / t_host:.1f} clients/sec {prog}", **stat_kw("host"))
    emit(f"engine_device_sync_{tag}m{m}", t_dev * 1e6,
         f"{m / t_dev:.1f} clients/sec ({t_host / t_dev:.2f}x vs pr1-engine) {prog}",
         **stat_kw("device"))
    emit(f"engine_async_{tag}m{m}", t_async * 1e6,
         f"{m / t_async:.1f} clients/sec {prog}", **stat_kw("async"))
    return {"loop": t_ref, "host": t_host, "device": t_dev, "async": t_async}


def bench_mesh(m: int, n_edges: int) -> Dict[str, float]:
    """Mesh-engine scale point: the device pipeline vs its shard_map
    counterpart over the visible devices.  With one visible device (the
    default process) this measures shard_map/ledger overhead, not a speedup
    — virtual CPU devices never run concurrently; the multi-device
    correctness + comm-accounting run lives in
    ``benchmarks/distributed_bench.py``."""
    from repro.engine.mesh_sim import MeshSyncEngine

    clients, assignment, test, _latency, program, _ = _make_population(m, n_edges)
    mk = dict(program=program, test=test, schedule=HFLSchedule(1, 1), seed=0)
    makers = {
        "device": lambda: BatchedSyncEngine(
            clients, assignment, pipeline="device", **mk
        ),
        "mesh": lambda: MeshSyncEngine(clients, assignment, **mk),
    }
    t = _time_interleaved(makers)
    t_dev = t["device"]["best_us"] * 1e-6
    t_mesh = t["mesh"]["best_us"] * 1e-6
    eng = MeshSyncEngine(clients, assignment, **mk)
    eng.run(1, eval_every=1)
    rep = eng.comm_report()
    emit(f"engine_mesh_m{m}", t_mesh * 1e6,
         f"{m / t_mesh:.1f} clients/sec ({t_dev / t_mesh:.2f}x vs device) "
         f"k={rep['devices']} xe/cloud={rep['cross_edge_bytes_per_cloud_round']:.3e} B",
         mean_us=t["mesh"]["mean_us"], std_us=t["mesh"]["std_us"],
         repeats=t["mesh"]["repeats"])
    return {"device": t_dev, "mesh": t_mesh}


def bench_faults(m: int, n_edges: int) -> Dict[str, float]:
    """Fault-injected scale point: clients/sec plus the wasted-bits fraction
    (bits that died in the air / all uplink airtime) under ~20% availability
    churn with lossy, async-retried uploads and finite energy budgets."""
    import jax

    from repro.faults import FaultSpec, FaultState
    from repro.utils.tree import tree_size_bytes
    from repro.wireless import WirelessParams, sample_topology

    spec = FaultSpec(seed=0, p_drop=0.2, p_rejoin=0.5, p_fail=0.15,
                     max_retries=2, backoff_s=0.05, energy_uploads=8.0,
                     refade_rounds=1, drift_rate=0.02)
    clients, assignment, test, latency, program, _ = _make_population(m, n_edges)
    topo = sample_topology(jax.random.PRNGKey(0), m, n_edges)
    wp = WirelessParams()
    bits = tree_size_bytes(program.init(jax.random.PRNGKey(0))) * 8

    def state():
        # fresh per engine instance: FaultState carries per-run energy
        # balances and dispatch counters
        return FaultState(spec, topo, wp, bits)

    mk = dict(program=program, test=test, schedule=HFLSchedule(1, 1), seed=0)
    makers = {
        "host": lambda: BatchedSyncEngine(
            clients, assignment, pipeline="host", faults=state(), **mk),
        "device": lambda: BatchedSyncEngine(
            clients, assignment, pipeline="device", faults=state(), **mk),
        "async": lambda: AsyncHFLEngine(
            clients, assignment, latency=latency, quorum=0.75,
            faults=state(), **mk),
        "loop": lambda: HFLSimulation(clients, assignment, faults=state(), **mk),
    }
    t = _time_interleaved(makers)
    out = {}
    for k, make_sim in makers.items():
        sim = make_sim()
        sim.run(1, eval_every=1)
        tot = sim.accountant.totals()
        frac = tot["wasted_bits"] / max(tot["eu_up_bits"] + tot["wasted_bits"], 1.0)
        best_s = t[k]["best_us"] * 1e-6
        emit(f"engine_faults_{k}_m{m}", t[k]["best_us"],
             f"{m / best_s:.1f} clients/sec wasted_frac={frac:.3f} "
             f"program={program.name} (20% churn, lossy uplinks)",
             mean_us=t[k]["mean_us"], std_us=t[k]["std_us"],
             repeats=t[k]["repeats"], wasted_frac=round(frac, 4))
        out[k] = frac
    return out


def bench_streaming() -> None:
    """Streaming-population scale sweep: M = 100k and 1M, fresh process per
    point (``ru_maxrss`` is a process-lifetime high-water mark — see
    ``benchmarks/streaming_point.py``).  The acceptance shape: peak RSS flat
    in M (the engine holds O(cohort) data + ~8 bytes/client of int32
    metadata) and clients/sec a function of cohort size, not M.

    Each child takes the device itself, so the parent must not have touched
    JAX: run it as ``python -m benchmarks.engine_bench --streaming``."""
    import json as _json
    import subprocess
    import sys as _sys

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "bench_streaming starts one JAX process per point; this process "
            "already holds a JAX backend (and with it any accelerator)"
        )
    sizes = [100_000, 1_000_000]
    cohort, rounds = (64, 2) if QUICK else (256, 5)
    points = []
    for m in sizes:
        cmd = [
            _sys.executable, "-m", "benchmarks.streaming_point",
            "--m", str(m), "--cohort", str(cohort), "--rounds", str(rounds),
        ]
        out = subprocess.run(cmd, capture_output=True, text=True, check=True)
        p = _json.loads(out.stdout.strip().splitlines()[-1])
        points.append(p)
        emit(
            f"engine_stream_m{m}",
            p["wall_s"] / rounds * 1e6,
            f"{p['clients_per_sec']:.1f} clients/sec cohort={cohort} "
            f"rss={p['peak_rss_bytes'] / 1e6:.0f}MB program=cnn-micro",
            peak_rss_bytes=p["peak_rss_bytes"],
            device_bytes=p["device_bytes"],
            page_misses=p["page_misses"],
            page_evictions=p["page_evictions"],
            cohort=cohort,
            m=m,
        )
    rss = [x["peak_rss_bytes"] for x in points]
    ratio = max(rss) / min(rss)
    emit(
        "engine_stream_mem_flatness", 0.0,
        f"peak-RSS max/min {ratio:.3f} across M=100k..1M (target <= 1.10)",
        mem_ratio=round(ratio, 4),
    )


def main(model: Optional[str] = None) -> None:
    start = mark()
    if model is None:
        # default suite: the CNN trajectory at every scale, plus one MLP
        # scale point (quick mode included) so CI tracks a non-CNN program
        # and one fault-injected point so the degraded paths stay timed
        sizes = [18, 128, 512, 2048]
        n_edges = {18: 5, 128: 8, 512: 8, 2048: 8}
        for m in sizes:
            bench_scale(m, n_edges[m])
        bench_scale(128, 8, model="mlp")
        bench_faults(128, 8)
        dump_json("BENCH_engine.json", start)
    else:
        sizes = {
            "cnn": [18, 128, 512, 2048],
            "mlp": [18, 128, 512],
            "lm": [18, 128],
            # the heavy sequence models stay at the IoT population size in
            # quick mode (CI); BENCH_FULL=1 adds the batching-regime point
            "moe": [18] if QUICK else [18, 128],
            "mamba": [18] if QUICK else [18, 128],
            "rwkv": [18] if QUICK else [18, 128],
            "mix": [18, 128] if QUICK else [18, 128, 512],
        }
        for m in sizes[model]:
            bench_scale(m, 8 if m > 18 else 5, model=model)
        # single-model sweeps land in their own file so they never clobber
        # the PR-tracked default-suite trajectory in BENCH_engine.json
        dump_json(f"BENCH_engine_{model}.json", start)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None,
                    choices=["cnn", "mlp", "lm", "moe", "mamba", "rwkv", "mix"],
                    help="bench one program's scale sweep (default: CNN suite "
                         "+ MLP point; 'mix' = cnn+mlp hetero population with "
                         "the distillation fuse)")
    ap.add_argument("--faults", action="store_true",
                    help="bench ONLY the fault-injected scale point (20% "
                         "churn, lossy retried uplinks, finite batteries)")
    ap.add_argument("--streaming", action="store_true",
                    help="bench ONLY the streaming-population scale sweep "
                         "(M=100k and 1M, lazy shards, cohort sampling, "
                         "paged store; one subprocess per point)")
    ap.add_argument("--mesh", action="store_true",
                    help="bench ONLY the mesh-engine scale point (shard_map "
                         "over the visible devices vs the device pipeline)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.faults:
        start = mark()
        bench_faults(128, 8)
        dump_json("BENCH_engine_faults.json", start)
    elif args.mesh:
        start = mark()
        bench_mesh(128, 8)
        dump_json("BENCH_engine_mesh.json", start)
    elif args.streaming:
        start = mark()
        bench_streaming()
        dump_json("BENCH_engine_streaming.json", start)
    else:
        main(model=args.model)

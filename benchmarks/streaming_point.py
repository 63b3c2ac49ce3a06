"""One streaming scale point, run in a FRESH process — prints one JSON line.

``ru_maxrss`` is a process-lifetime high-water mark: it never decreases, so
a single process sweeping M = 100k then 1M would report the 100k point's
memory as "at least whatever 1M peaked at" (or vice versa, the larger point
hiding behind an earlier allocation).  Run this module once per (M, cohort)
point and read its JSON line; each point's ``peak_rss_bytes`` is then the
true footprint of building + running the streaming engine at that M and
nothing else:

    PYTHONPATH=src python -m benchmarks.streaming_point --m 100000 --cohort 64

The workload is a micro-CNN regime (seq 64, ~4k params) on a
:class:`~repro.data.shard_source.HealthShardSource` population with striped
assignment.
"""
from __future__ import annotations

import argparse
import json
import time


def run_point(
    m: int,
    cohort: int,
    rounds: int = 2,
    n_edges: int = 8,
    seed: int = 0,
    page_slots: int = None,
    strategy: str = "uniform",
) -> dict:
    import numpy as np

    from benchmarks.common import device_buffer_bytes, peak_rss_bytes
    from repro.data.shard_source import HealthShardSource
    from repro.data.synthetic_health import make_dataset
    from repro.engine import StreamSyncEngine
    from repro.federated.programs import CNNProgram
    from repro.federated.sampling import CohortSpec
    from repro.federated.stream import striped_assignment
    from repro.models.cnn1d import CNNConfig

    cfg = CNNConfig(in_channels=1, n_classes=5, seq_len=64, c1=8, c2=8, hidden=16)
    t0 = time.perf_counter()
    source = HealthShardSource(
        seed, m, n_classes=cfg.n_classes, length=cfg.seq_len,
        channels=cfg.in_channels,
    )
    edge_of = striped_assignment(source, n_edges)
    test = make_dataset(
        np.random.default_rng((seed, 0x7E57)), np.full(cfg.n_classes, 20),
        length=cfg.seq_len, channels=cfg.in_channels,
    )
    eng = StreamSyncEngine(
        source, edge_of, CNNProgram(cfg), test,
        cohort=CohortSpec(size=cohort, strategy=strategy, seed=seed),
        n_edges=n_edges, seed=seed, page_slots=page_slots,
    )
    build_s = time.perf_counter() - t0
    eng.run(1, eval_every=1)  # warmup: compile + first paging wave
    t0 = time.perf_counter()
    eng.run(rounds, eval_every=rounds)
    wall_s = time.perf_counter() - t0
    return {
        "m": m,
        "cohort": cohort,
        "rounds": rounds,
        "build_s": round(build_s, 3),
        "wall_s": round(wall_s, 4),
        "clients_per_sec": round(cohort * rounds / wall_s, 1),
        "peak_rss_bytes": peak_rss_bytes(),
        "device_bytes": device_buffer_bytes(),
        "page_hits": eng.store.hits,
        "page_misses": eng.store.misses,
        "page_evictions": eng.store.evictions,
    }


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, required=True)
    ap.add_argument("--cohort", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--n-edges", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-slots", type=int, default=None)
    ap.add_argument("--strategy", default="uniform")
    args = ap.parse_args()
    print(json.dumps(run_point(
        args.m, args.cohort, rounds=args.rounds, n_edges=args.n_edges,
        seed=args.seed, page_slots=args.page_slots, strategy=args.strategy,
    )))

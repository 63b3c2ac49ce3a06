"""The benchmark's own copy of the traffic generators.

Kept apart from the program so that a later change to the program cannot
move the yardstick: the plain reference trains on shards made here, and a
program whose own generator drifts from these no longer matches it.  Each
function reproduces, draw for draw, the generator of the deployment it
stands for: here the paper's Table-3 heartbeat population, as the eager
``build_scenario`` draws it.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

# Table 3 of arXiv:2107.06548: heartbeat instances per edge and class
TABLE3_HEARTBEAT = np.array(
    [
        [10, 10, 0, 0, 0],
        [0, 0, 10, 10, 0],
        [10, 0, 0, 0, 10],
        [0, 10, 10, 0, 0],
        [0, 0, 0, 10, 10],
    ],
    dtype=np.int64,
) * 1000


@dataclasses.dataclass
class Shard:
    x: np.ndarray  # (n, length, channels) float32
    y: np.ndarray  # (n,) int32

    def __len__(self) -> int:
        return len(self.y)


def _class_signal(rng, cls: int, n: int, length: int, channels: int) -> np.ndarray:
    t = np.linspace(0, 1, length, dtype=np.float32)
    base_freq = 2.0 + 3.0 * cls
    phase = rng.uniform(0, 2 * np.pi, (n, 1, 1)).astype(np.float32)
    amp = (0.8 + 0.4 * rng.random((n, 1, 1))).astype(np.float32)
    chan_mix = (1.0 + 0.3 * np.sin(np.arange(channels) * (cls + 1))).astype(np.float32)
    sig = amp * np.sin(2 * np.pi * base_freq * t[None, :, None] + phase)
    center = int(length * (0.2 + 0.15 * cls))
    width = max(3, length // 40)
    spike = np.exp(-0.5 * ((np.arange(length) - center) / width) ** 2).astype(np.float32)
    sig = sig + (1.5 + 0.5 * cls) * spike[None, :, None]
    sig = sig * chan_mix[None, None, :]
    noise = rng.normal(0, 0.35, (n, length, channels)).astype(np.float32)
    return sig + noise


def make_dataset(rng, class_counts, length: int, channels: int) -> Shard:
    """Per-class sinusoid + spike signals with noise, shuffled."""
    xs, ys = [], []
    for cls, cnt in enumerate(np.asarray(class_counts, dtype=int)):
        if cnt <= 0:
            continue
        xs.append(_class_signal(rng, cls, cnt, length, channels))
        ys.append(np.full((cnt,), cls, np.int32))
    x = np.concatenate(xs, 0)
    y = np.concatenate(ys, 0)
    perm = rng.permutation(len(y))
    return Shard(x[perm], y[perm])


def _eus_per_edge(n_edges: int, n_eus: int) -> List[int]:
    base = n_eus // n_edges
    extra = n_eus - base * n_edges
    return [base + (1 if j < extra else 0) for j in range(n_edges)]


def _eu_counts(rng, table: np.ndarray, eus_per_edge: List[int], scale: float) -> np.ndarray:
    """Split each edge's class totals over its EUs (Dirichlet(0.5) shares)."""
    counts = []
    for j in range(table.shape[0]):
        frac = rng.dirichlet(np.ones(eus_per_edge[j]) * 0.5, size=table.shape[1]).T
        tot = np.maximum((table[j] * scale).astype(np.int64), 0)
        cc = np.floor(frac * tot[None, :]).astype(np.int64)
        cc[0] += tot - cc.sum(axis=0)
        counts.append(cc)
    return np.concatenate(counts, 0)


def _split_by_counts(rng, ds: Shard, counts: np.ndarray, n_classes: int) -> List[Shard]:
    pools = {c: list(rng.permutation(np.nonzero(ds.y == c)[0])) for c in range(n_classes)}
    shards = []
    for i in range(counts.shape[0]):
        take = []
        for c in range(n_classes):
            n = int(counts[i, c])
            take.extend(pools[c][:n])
            pools[c] = pools[c][n:]
        idx = np.asarray(take, dtype=int)
        shards.append(Shard(ds.x[idx], ds.y[idx]))
    return shards


def table3_population(seed: int, scale: float, n_eus: int = 18) -> Tuple[List[Shard], np.ndarray]:
    """The paper's heartbeat federation: (per-EU shards, (M, K) class counts),
    drawn in the order of the eager scenario builder."""
    rng = np.random.default_rng(seed)
    table = TABLE3_HEARTBEAT
    counts = _eu_counts(rng, table, _eus_per_edge(table.shape[0], n_eus), scale)
    train = make_dataset(rng, counts.sum(axis=0), 187, 1)
    return _split_by_counts(rng, train, counts, table.shape[1]), counts


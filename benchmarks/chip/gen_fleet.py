"""The benchmark's own copy of the streaming fleet's generators.

Kept apart from the program for the reason ``gen.py`` gives: the plain
reference trains the clients, on the shards, that these functions name, so
a program whose own generator or cohort draw drifts from them no longer
matches it.  Each function reproduces, draw for draw, the program's lazy
health population (``build_scenario(lazy=True)``: splitmix64-keyed class
counts, per-client keyed signal synthesis) and its uniform cohort
(``CohortSpec(size, "uniform")``: Floyd's draw from a generator keyed by
the cloud and edge round, every client eligible).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from gen import Shard, make_dataset

# stream tags of the program's keyed draws
S_COUNTS = 0x5EED_0001  # per-(client, class) base count
S_DOM = 0x5EED_0002  # per-client dominant class
S_DATA = 0x5EED_0003  # shard-content generator key
S_COHORT = 0xC0_4081  # cohort generator key

_U64 = np.uint64
_GAMMA = _U64(0x9E3779B97F4A7C15)
_M1 = _U64(0xBF58476D1CE4E5B9)
_M2 = _U64(0x94D049BB133111EB)
_CHUNK = 1 << 16


def splitmix64(x) -> np.ndarray:
    z = np.asarray(x, dtype=_U64)
    with np.errstate(over="ignore"):
        z = (z + _GAMMA) & ~_U64(0)
        z = (z ^ (z >> _U64(30))) * _M1
        z = (z ^ (z >> _U64(27))) * _M2
        z = z ^ (z >> _U64(31))
    return z


def keyed_hash(seed: int, stream: int, idx) -> np.ndarray:
    """uint64 per element of ``idx``: seed mixed in one round, stream in the next."""
    idx = np.asarray(idx, dtype=_U64)
    with np.errstate(over="ignore"):
        h = splitmix64(idx ^ splitmix64(np.asarray(_U64(seed & 0xFFFFFFFFFFFFFFFF))))
        h = splitmix64(h + _U64(stream & 0xFFFFFFFFFFFFFFFF) * _GAMMA)
    return h


def keyed_randint(seed: int, stream: int, idx, n: int) -> np.ndarray:
    return (keyed_hash(seed, stream, idx) % _U64(n)).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class HealthFleet:
    """M clients whose class counts are a base count per class, hashed into
    ``[min_per_class, max_per_class]``, plus ``dom_boost`` samples of a
    hashed dominant class; each shard's signals come from a generator keyed
    by ``(seed, S_DATA, cid)``."""

    seed: int
    n_clients: int
    n_classes: int
    length: int
    channels: int
    min_per_class: int
    max_per_class: int
    dom_boost: int

    def class_counts(self, lo: int, hi: int) -> np.ndarray:
        """(hi - lo, K) int64 class counts of clients lo..hi-1."""
        cids = np.arange(lo, hi, dtype=np.int64)
        k = self.n_classes
        lanes = cids[:, None] * k + np.arange(k)[None, :]
        span = self.max_per_class - self.min_per_class + 1
        counts = (keyed_hash(self.seed, S_COUNTS, lanes.ravel()).reshape(len(cids), k)
                  % _U64(span)).astype(np.int64) + self.min_per_class
        counts[np.arange(len(cids)), keyed_randint(self.seed, S_DOM, cids, k)] += self.dom_boost
        return counts

    def sizes(self) -> np.ndarray:
        """(M,) int32 shard sizes."""
        out = np.empty(self.n_clients, np.int32)
        for lo in range(0, self.n_clients, _CHUNK):
            hi = min(lo + _CHUNK, self.n_clients)
            out[lo:hi] = self.class_counts(lo, hi).sum(axis=1)
        return out

    def shard(self, cid: int) -> Shard:
        cid = int(cid)
        rng = np.random.default_rng((self.seed, S_DATA, cid))
        return make_dataset(rng, self.class_counts(cid, cid + 1)[0], self.length, self.channels)


def uniform_cohort(seed: int, size: int, m: int, cloud_round: int, edge_round: int) -> np.ndarray:
    """Sorted ids of round (cloud_round, edge_round)'s uniform cohort of
    ``size`` out of clients 0..m-1 (all eligible): Floyd's draw."""
    k = min(size, m)
    if k == m:
        return np.arange(m, dtype=np.int64)
    rs = np.random.default_rng((seed, S_COHORT, int(cloud_round), int(edge_round)))
    chosen = set()
    for j in range(m - k, m):
        t = int(rs.integers(0, j + 1))
        chosen.add(j if t in chosen else t)
    return np.sort(np.fromiter(chosen, np.int64, k))

"""Device-resident client shard store.

``run_cohorts`` originally gathered every cohort batch on the host — a
python list comprehension over C clients' numpy shards, an ``np.stack``,
and a fresh host->device upload of the full batch tensor per epoch of
every round.  At M >= 512 that host loop is the dominant per-round cost
once training itself is batched.

``DeviceShardStore`` pads all client shards into ONE ``(M, n_max, *feat)``
device array at engine construction (a one-time cost outside the round
loop).  The feature block is whatever the client program trains on — rank
and dtype are taken from the shards themselves: ``(L, Ch)`` float32
signals for the CNN/MLP programs, ``(S,)`` int32 token sequences for the
LM.  Per-step batches are then assembled by a single jitted gather from
sample indices: the only host->device traffic per epoch is the small
``(C, steps, batch)`` int32 index tensor the RNG stream produces anyway.

Indices are always drawn in ``[0, len(shard_i))`` (the reference sampling
resamples within the shard), so the zero padding rows are never read.

Padding is to the LARGEST shard: memory is O(M * n_max).  With the IoT
populations this engine targets (many small, similar shards) the overhead
is bounded, but one pathologically large shard inflates the store M-fold —
``padding_ratio`` reports the blow-up, the async engine skips the store
past ``MAX_PADDING_RATIO``, and the sync engine's ``pipeline="host"``
avoids the store entirely.
"""
from __future__ import annotations

import os
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.shard_source import ShardSource
from repro.telemetry import NULL_TELEMETRY, register_jit

# past this pad blow-up the store costs more memory than it saves time;
# callers that can fall back to host batch stacking (async engine) do so
MAX_PADDING_RATIO = 16.0

# page-in synthesis threads: the random draws and the vectorised signal
# arithmetic release the GIL, the per-client loop holds it; on a TPU v5e
# host synthesis scaled up to 8 threads and slowed past them
_SYNTH_MAX_THREADS = 8
# miss batches under this many rows are synthesised inline: handing a few
# dozen shards to a pool costs more than it saves
_SYNTH_INLINE_ROWS = 64


def _synth_threads() -> int:
    """Host threads for page-in synthesis: the CPUs this process may run on,
    capped at ``_SYNTH_MAX_THREADS``."""
    try:
        n = len(os.sched_getaffinity(0))
    except AttributeError:  # platforms without CPU affinity
        n = os.cpu_count() or 1
    return max(1, min(n, _SYNTH_MAX_THREADS))


@jax.jit
def _store_gather(x, y, cids, idx):
    """x: (M, n_max, *feat); y: (M, n_max); cids: (C,); idx: (C, S, B).

    Returns (C, S, B, *feat) batches and (C, S, B) labels in one gather;
    the advanced-index broadcast is rank-agnostic over the feature block.
    """
    c = cids[:, None, None]
    return x[c, idx], y[c, idx]


class DeviceShardStore:
    """All client shards padded into one device-resident array pair.

    ``clients`` is a sequence of ``FLClient``-like objects ordered by
    ``cid`` (checked — :meth:`gather` indexes by cid).  The feature block's
    rank and dtype follow the shards themselves, which must agree across
    clients: ``(L, Ch)`` float32 signals for the CNN/MLP programs, ``(S,)``
    int32 token sequences for the sequence programs (lm/moe/mamba/rwkv) —
    any uniform layout a ``ClientProgram.feat_shape``/``feat_dtype``
    describes works.  Labels are always int32.
    """

    def __init__(self, clients: Sequence):
        if not clients:
            raise ValueError("DeviceShardStore needs at least one client")
        for i, c in enumerate(clients):
            if getattr(c, "cid", i) != i:
                # gather() is indexed by cid; a reordered client list would
                # silently train on the wrong shards
                raise ValueError(f"client at position {i} has cid {c.cid}")
        self._build([c.shard for c in clients])

    @classmethod
    def from_shards(cls, shards: Sequence):
        """Store over bare ``Dataset`` shards, indexed by position.

        The distillation layer keeps each edge's PUBLIC shard device-resident
        this way (row = edge id); there are no client objects to take cids
        from, so rows simply follow the sequence order.
        """
        obj = cls.__new__(cls)
        obj._build(list(shards))
        return obj

    def _build(self, shards: List) -> None:
        if not shards:
            raise ValueError("DeviceShardStore needs at least one shard")
        self.sizes = np.array([len(s) for s in shards], np.int64)
        n_max = max(1, int(self.sizes.max()))
        feat = None
        for s in shards:
            if len(s):
                feat = s.x.shape[1:]
                break
        if feat is None:  # every shard empty: 1-sample zero store, never read
            feat = shards[0].x.shape[1:]
        # feature dtype follows the data: float signals or int token ids
        xs = np.zeros((len(shards), n_max) + tuple(feat), shards[0].x.dtype)
        ys = np.zeros((len(shards), n_max), np.int32)
        for i, s in enumerate(shards):
            if len(s) == 0:
                continue
            if s.x.shape[1:] != feat:
                raise ValueError(
                    f"client {i} shard shape {s.x.shape[1:]} != store layout {feat}"
                )
            xs[i, : len(s)] = s.x
            ys[i, : len(s)] = s.y
        self.x = jnp.asarray(xs)
        self.y = jnp.asarray(ys)

    @property
    def n_clients(self) -> int:
        return int(self.x.shape[0])

    @property
    def padding_ratio(self) -> float:
        """Padded cells per real sample (1.0 = perfectly uniform shards)."""
        total = max(1, int(self.sizes.sum()))
        return self.x.shape[0] * self.x.shape[1] / total

    @classmethod
    def build_if_economical(cls, clients: Sequence):
        """Store, or None when padding would blow memory past
        ``MAX_PADDING_RATIO`` (one huge shard among many small ones).
        The ratio is checked BEFORE any allocation."""
        sizes = np.array([len(c.shard) for c in clients] or [0])
        ratio = len(sizes) * max(1, int(sizes.max())) / max(1, int(sizes.sum()))
        return cls(clients) if ratio <= MAX_PADDING_RATIO else None

    def gather(self, cids, idx) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """cids: (C,) client ids; idx: (C, steps, batch) in-shard indices."""
        return _store_gather(
            self.x, self.y, jnp.asarray(cids, jnp.int32), jnp.asarray(idx, jnp.int32)
        )


class PagedShardStore:
    """Bounded device working set over a lazy :class:`ShardSource`.

    ``DeviceShardStore`` is O(M) device memory — at M=1M the padded
    ``(M, n_max, *feat)`` array alone dwarfs any host.  The paged store
    keeps a fixed ``(capacity, n_max, *feat)`` slab plus an LRU slot map:
    :meth:`gather` first *ensures* the round's cohort is resident (one
    batched host->device scatter for the misses, shards synthesized on
    demand from the source), then runs the same jitted ``_store_gather``
    over slot ids instead of client ids.  Memory is O(cohort), not O(M),
    and because ``source.shard(cid)`` is pure in ``(seed, cid)``, an
    evicted client rehydrates bit-identically later.

    ``capacity`` should comfortably exceed the cohort size (a cohort larger
    than the slab cannot be resident at once and raises).  Hit/miss/eviction
    counters expose paging behaviour to benchmarks and tests.  Client ids
    within one ``ensure`` call must be unique (cohorts are).

    Missed shards are written straight into their rows of one miss batch
    by ``source.write_shards``; a batch of at least ``_SYNTH_INLINE_ROWS``
    misses is split into row ranges run on a pool of host threads
    (:func:`_synth_threads` of them, made on first use; :meth:`close` ends
    them).  The bytes do not depend on the thread count.

    With an enabled ``telemetry`` each :meth:`ensure` is a ``page_in`` span
    (attrs ``clients``, ``misses``, ``hits``, ``synth_s``: the writer's wall
    time, ``workers``: the threads it ran on; its uploads go through
    ``telemetry.upload``, so its ``h2d_bytes`` are the bytes paged in) and
    adds ``page_in_s``, ``page_synth_s``, ``page_misses``, ``page_hits``
    and ``page_in_bytes`` to the span open around the call.
    """

    def __init__(self, source, capacity: int, n_max: "int | None" = None,
                 telemetry=NULL_TELEMETRY):
        sizes = np.asarray(source.sizes)
        if len(sizes) == 0:
            raise ValueError("PagedShardStore needs a non-empty source")
        self.source = source
        self.sizes = sizes
        self.capacity = int(min(capacity, len(sizes)))
        if self.capacity < 1:
            raise ValueError("PagedShardStore needs capacity >= 1")
        self.n_max = int(n_max if n_max is not None else max(1, sizes.max()))
        feat = tuple(source.feat_shape)
        self._feat = feat
        self._np_dtype = np.dtype(source.feat_dtype)
        self.x = jnp.zeros((self.capacity, self.n_max) + feat, self._np_dtype)
        self.y = jnp.zeros((self.capacity, self.n_max), jnp.int32)
        self._slot_of: dict = {}  # cid -> slot
        self._lru: OrderedDict = OrderedDict()  # cid -> None, order = recency
        self._free = list(range(self.capacity - 1, -1, -1))
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.tel = telemetry
        self._threads = _synth_threads()
        self._pool = None  # ThreadPoolExecutor, made by the first pooled batch

    @classmethod
    def from_shards(cls, shards: Sequence, capacity: int):
        """Paged store over already-materialized shards (parity tests)."""
        return cls(_ShardListSource(list(shards)), capacity)

    @property
    def device_bytes(self) -> int:
        return int(self.x.nbytes) + int(self.y.nbytes)

    @property
    def n_clients(self) -> int:
        return len(self.sizes)

    def close(self) -> None:
        """End the synthesis threads; a later pooled batch makes new ones."""
        if self._pool is not None:
            self._pool.shutdown()
            self._pool = None

    def _take_slot(self) -> int:
        if self._free:
            return self._free.pop()
        victim, _ = self._lru.popitem(last=False)
        self.evictions += 1
        return self._slot_of.pop(victim)

    def ensure(self, cids) -> np.ndarray:
        """Page the given clients in; return their (C,) slot ids.

        Residents are touched (moved to MRU) *before* any eviction, so a
        miss can never evict a slot this same call needs.
        """
        cids = np.asarray(cids, np.int64)
        if len(cids) > self.capacity:
            raise ValueError(
                f"cohort of {len(cids)} exceeds paged-store capacity {self.capacity}"
            )
        tel = self.tel
        with tel.span("page_in", clients=len(cids)) as sp:
            slots, misses, synth_s, workers = self._page_in(cids)
            sp.set(misses=misses, hits=len(cids) - misses, synth_s=synth_s, workers=workers)
        if tel.enabled:
            for key, value in (("page_in_s", sp.duration), ("page_synth_s", synth_s),
                               ("page_misses", misses),
                               ("page_hits", len(cids) - misses),
                               ("page_in_bytes", sp.attrs.get("h2d_bytes", 0))):
                tel.tracer.add_to_open(key, value)
        return slots

    def _page_in(self, cids: np.ndarray) -> Tuple[np.ndarray, int, float, int]:
        """(slots, misses, synthesis seconds, synthesis threads) of
        :meth:`ensure`."""
        slots = np.empty(len(cids), np.int64)
        missing: List[int] = []
        for p, c in enumerate(cids.tolist()):
            s = self._slot_of.get(c)
            if s is None:
                missing.append(p)
            else:
                slots[p] = s
                self.hits += 1
                self._lru.move_to_end(c)
        if not missing:
            return slots, 0, 0.0, 0
        mc = cids[missing]
        n = self.sizes[mc]
        if (n > self.n_max).any():
            bad = int(np.argmax(n > self.n_max))
            raise ValueError(
                f"shard {int(mc[bad])} ({int(n[bad])} samples) exceeds n_max {self.n_max}"
            )
        for p, c in zip(missing, mc.tolist()):
            s = self._take_slot()
            self._slot_of[c] = s
            self._lru[c] = None
            slots[p] = s
            self.misses += 1
        # one batched scatter per ensure(): host->device traffic is the
        # round's misses only, never the population.  The miss batch is
        # padded to a power of two (floor 16, capped at capacity) by
        # repeating row 0 — same slot, same data, so the duplicate writes
        # are idempotent — because a scatter compiles per distinct row
        # count and miss counts vary every round.
        k = len(missing)
        kp = min(16 if k <= 16 else 1 << (k - 1).bit_length(), self.capacity)
        bx = np.zeros((kp, self.n_max) + self._feat, self._np_dtype)
        by = np.zeros((kp, self.n_max), np.int32)
        t0 = time.perf_counter()
        workers = self._synthesise(mc, bx, by)
        synth_s = time.perf_counter() - t0
        bx[k:] = bx[0]
        by[k:] = by[0]
        ms = np.empty(kp, np.int64)
        ms[:k] = slots[missing]
        ms[k:] = ms[0]
        sl = self.tel.upload(ms, np.int32)
        self.x = self.x.at[sl].set(self.tel.upload(bx))
        self.y = self.y.at[sl].set(self.tel.upload(by))
        return slots, k, synth_s, workers

    def _synthesise(self, cids: np.ndarray, bx: np.ndarray, by: np.ndarray) -> int:
        """Write the shards of ``cids`` into the first rows of ``bx``/``by``;
        returns the threads used.  Threads write disjoint row ranges."""
        k = len(cids)
        threads = self._threads if k >= _SYNTH_INLINE_ROWS else 1
        if threads == 1:
            self.source.write_shards(cids, bx, by)
            return 1
        if self._pool is None:
            self._pool = ThreadPoolExecutor(threads, thread_name_prefix="page-in")
        # a few ranges per thread, so uneven shard sizes even out
        bounds = np.linspace(0, k, 4 * threads + 1).astype(int).tolist()
        futures = [
            self._pool.submit(self.source.write_shards, cids[a:b], bx[a:b], by[a:b])
            for a, b in zip(bounds[:-1], bounds[1:])
            if b > a
        ]
        for f in futures:
            f.result()
        return threads

    def gather(self, cids, idx) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """cids: (C,) client ids; idx: (C, steps, batch) in-shard indices."""
        slots = self.ensure(cids)
        return _store_gather(
            self.x, self.y, jnp.asarray(slots, jnp.int32), jnp.asarray(idx, jnp.int32)
        )


class _ShardListSource(ShardSource):
    """Minimal ShardSource adapter over an in-memory shard list."""

    def __init__(self, shards: List):
        self._shards = shards
        self.n_clients = len(shards)
        self._sizes = np.array([len(s) for s in shards], np.int64)
        feat = None
        for s in shards:
            if len(s):
                feat = s.x.shape[1:]
                dtype = s.x.dtype
                break
        if feat is None:
            feat, dtype = shards[0].x.shape[1:], shards[0].x.dtype
        self.feat_shape = tuple(feat)
        self.feat_dtype = dtype

    def shard(self, cid: int):
        return self._shards[cid]


register_jit("store_gather", _store_gather)

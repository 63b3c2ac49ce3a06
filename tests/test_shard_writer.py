"""The batched shard writer and the paged store's threaded page-in.

``ShardSource.write_shards`` writes clients' shards straight into rows of a
miss batch; ``HealthShardSource`` overrides it with one vectorised count
hash and a per-client writer over hoisted per-class constants, and
``PagedShardStore`` runs it over row ranges on a pool of host threads.
None of that may change a byte: the writer equals ``shard(cid)``, the
signal bank equals the per-class loop it replaced, and the slab is the
same for any thread count.
"""
import os
import sys
import threading

import numpy as np
import pytest

from repro.data.shard_source import _S_DATA, HealthShardSource, ShardSource, TokenShardSource
from repro.data.synthetic_health import make_dataset
from repro.engine import PagedShardStore
from repro.engine.store import _SYNTH_INLINE_ROWS, _SYNTH_MAX_THREADS, _synth_threads
from repro.telemetry import Telemetry

# (length, channels, classes): the seizure fleet's EEG and the heartbeat ECG
LAYOUTS = [(178, 19, 3), (187, 1, 5)]


def _loop_make_dataset(rng, class_counts, length, channels):
    """The per-class loop ``make_dataset`` was before the signal bank."""
    xs, ys = [], []
    for cls, cnt in enumerate(np.asarray(class_counts, dtype=int)):
        if cnt <= 0:
            continue
        t = np.linspace(0, 1, length, dtype=np.float32)
        base_freq = 2.0 + 3.0 * cls
        phase = rng.uniform(0, 2 * np.pi, (cnt, 1, 1)).astype(np.float32)
        amp = (0.8 + 0.4 * rng.random((cnt, 1, 1))).astype(np.float32)
        chan_mix = (1.0 + 0.3 * np.sin(np.arange(channels) * (cls + 1))).astype(np.float32)
        sig = amp * np.sin(2 * np.pi * base_freq * t[None, :, None] + phase)
        center = int(length * (0.2 + 0.15 * cls))
        width = max(3, length // 40)
        spike = np.exp(-0.5 * ((np.arange(length) - center) / width) ** 2).astype(np.float32)
        sig = sig + (1.5 + 0.5 * cls) * spike[None, :, None]
        sig = sig * chan_mix[None, None, :]
        noise = rng.normal(0, 0.35, (cnt, length, channels)).astype(np.float32)
        xs.append(sig + noise)
        ys.append(np.full((cnt,), cls, np.int32))
    x = np.concatenate(xs, 0)
    y = np.concatenate(ys, 0)
    perm = rng.permutation(len(y))
    return x[perm], y[perm]


def _fleet(seed, layout, n_clients=10**6):
    length, channels, k = layout
    return HealthShardSource(
        seed, n_clients, n_classes=k, length=length, channels=channels,
        max_per_class=2, dom_boost=8,
    )


@pytest.mark.parametrize("layout", LAYOUTS)
def test_make_dataset_matches_the_per_class_loop(layout):
    """Counts with zeros and negatives, one class alone, a test-set size."""
    length, channels, k = layout
    draw = np.random.default_rng(1)
    cases = [draw.integers(-1, 5, k) for _ in range(40)] + [
        np.eye(k, dtype=int)[0] * 3, np.full(k, 60)]
    for i, counts in enumerate(cases):
        if counts[counts > 0].sum() == 0:
            counts = counts.copy()
            counts[-1] = 2
        want_x, want_y = _loop_make_dataset(np.random.default_rng(i), counts, length, channels)
        got = make_dataset(np.random.default_rng(i), counts, length, channels)
        assert got.x.dtype == np.float32 and got.y.dtype == np.int32
        assert got.n_classes == k
        np.testing.assert_array_equal(got.x, want_x)
        np.testing.assert_array_equal(got.y, want_y)


def test_make_dataset_without_samples_raises():
    with pytest.raises(ValueError, match="no positive count"):
        make_dataset(np.random.default_rng(0), [0, -1, 0], 16, 1)


@pytest.mark.parametrize("seed", [3160000011, 7])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_health_writer_equals_shard_bytes(seed, layout):
    """500 clients of a 10^6 fleet, many blocks of the batched writer: each
    row holds ``shard(cid)``'s bytes and the per-class loop's, ``x`` and
    ``y``, and the samples past its size stay zero; the batch has clients
    with an empty class and clients filling ``n_max`` exactly."""
    src = _fleet(seed, layout)
    cids = np.random.default_rng(seed).choice(src.n_clients, 500, replace=False)
    sizes = src.sizes[cids]
    n_max = int(sizes.max())
    x = np.zeros((len(cids), n_max) + src.feat_shape, np.float32)
    y = np.zeros((len(cids), n_max), np.int32)
    src.write_shards(cids, x, y)
    counts = src.class_counts_of(cids)
    assert (counts == 0).any(axis=1).any() and (sizes == n_max).any()
    for k, cid in enumerate(cids.tolist()):
        s = src.shard(cid)
        n = len(s)
        assert n == sizes[k]
        assert x[k, :n].tobytes() == s.x.tobytes()
        assert y[k, :n].tobytes() == s.y.tobytes()
        assert not x[k, n:].any() and not y[k, n:].any()
        want_x, want_y = _loop_make_dataset(
            np.random.default_rng((seed, _S_DATA, cid)), counts[k], layout[0], layout[1])
        assert x[k, :n].tobytes() == want_x.tobytes()
        assert y[k, :n].tobytes() == want_y.tobytes()


def test_class_counts_of_matches_the_block():
    src = _fleet(5, LAYOUTS[0], n_clients=5000)
    ids = np.array([4999, 0, 17, 17, 2500])
    np.testing.assert_array_equal(
        src.class_counts_of(ids), src.class_counts_block(0, 5000)[ids])


def _miss_waves(src, capacity, seed):
    """Three cohorts over the same slab: all misses, then a half-resident
    cohort that evicts."""
    rng = np.random.default_rng(seed)
    first = rng.choice(src.n_clients, capacity // 2, replace=False)
    fresh = np.setdiff1d(rng.choice(src.n_clients, capacity, replace=False), first)
    second = np.concatenate([first[: capacity // 4], fresh[: capacity // 2]])
    third = rng.choice(src.n_clients, capacity // 2, replace=False)
    return [first, second, third]


def _page(store, waves):
    slots = [store.ensure(c) for c in waves]
    return slots, np.asarray(store.x), np.asarray(store.y)


@pytest.mark.parametrize("threads", [2, 3, 8])
def test_slab_identical_for_any_thread_count(threads):
    """The slab, slot ids and counters after three waves of misses are the
    same whether the writer runs inline or over ``threads`` threads; the
    span says which; ``close`` ends the threads."""
    src = _fleet(11, (32, 2, 3), n_clients=50_000)
    waves = _miss_waves(src, 4 * _SYNTH_INLINE_ROWS, 11)
    inline = PagedShardStore(src, capacity=4 * _SYNTH_INLINE_ROWS)
    inline._threads = 1
    tel = Telemetry()
    pooled = PagedShardStore(src, capacity=4 * _SYNTH_INLINE_ROWS, telemetry=tel)
    pooled._threads = threads
    try:
        a_slots, a_x, a_y = _page(inline, waves)
        b_slots, b_x, b_y = _page(pooled, waves)
    finally:
        pooled.close()
    for a, b in zip(a_slots, b_slots):
        np.testing.assert_array_equal(a, b)
    assert a_x.tobytes() == b_x.tobytes() and a_y.tobytes() == b_y.tobytes()
    assert (inline.hits, inline.misses, inline.evictions) == (
        pooled.hits, pooled.misses, pooled.evictions)
    assert pooled.evictions > 0 and pooled.hits > 0
    pages = [s.attrs for s in tel.tracer.spans if s.name == "page_in"]
    assert [p["workers"] for p in pages] == [
        threads if p["misses"] >= _SYNTH_INLINE_ROWS else 1 for p in pages]
    assert all(0 < p["synth_s"] for p in pages)
    for t in threading.enumerate():
        if t.name.startswith("page-in"):
            t.join(timeout=10)
            assert not t.is_alive()


def test_pooled_writer_under_thread_contention():
    """More writer threads than cores, switching every microsecond: rows
    are disjoint, so the batch still equals the inline one."""
    src = _fleet(3, (24, 3, 3), n_clients=20_000)
    cids = np.random.default_rng(3).choice(src.n_clients, 3 * _SYNTH_INLINE_ROWS, replace=False)
    stores = [PagedShardStore(src, capacity=len(cids)) for _ in range(2)]
    stores[0]._threads = 1
    stores[1]._threads = min(len(os.sched_getaffinity(0)) + 2, 34)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        out = [_page(st, [cids]) for st in stores]
    finally:
        sys.setswitchinterval(old)
        stores[1].close()
    assert out[0][1].tobytes() == out[1][1].tobytes()
    assert out[0][2].tobytes() == out[1][2].tobytes()


@pytest.mark.parametrize("misses", [_SYNTH_INLINE_ROWS // 4, 2 * _SYNTH_INLINE_ROWS])
def test_token_store_pages_in_through_the_base_writer(misses):
    """The LM source keeps the base writer; its slab rows are ``shard``'s
    tokens, inline and pooled."""
    src = TokenShardSource(2, 5000, n_topics=3, vocab_size=64, seq_len=8)
    assert type(src).write_shards is ShardSource.write_shards
    cids = np.random.default_rng(2).choice(src.n_clients, misses, replace=False)
    st = PagedShardStore(src, capacity=misses)
    st._threads = 4
    try:
        slots = st.ensure(cids)
    finally:
        st.close()
    x, y = np.asarray(st.x), np.asarray(st.y)
    for cid, s in zip(cids.tolist(), slots.tolist()):
        sh = src.shard(cid)
        n = len(sh)
        np.testing.assert_array_equal(x[s, :n], sh.x)
        np.testing.assert_array_equal(y[s, :n], sh.y)
        assert not x[s, n:].any()


def test_oversized_shard_raises_before_any_slot_moves():
    src = _fleet(4, (16, 1, 3), n_clients=100)
    st = PagedShardStore(src, capacity=8, n_max=9)
    small = np.flatnonzero(src.sizes <= 9)[:3]
    big = int(np.flatnonzero(src.sizes > 9)[0])
    st.ensure(small)
    before = (st.hits, st.misses, st.evictions, dict(st._slot_of))
    with pytest.raises(ValueError, match=f"shard {big} "):
        st.ensure(np.append(small[:1], big))
    assert (st.hits, st.misses, st.evictions, dict(st._slot_of)) == (
        before[0] + 1, *before[1:])


def test_synth_threads_follow_the_affinity_mask():
    n = len(os.sched_getaffinity(0))
    assert _synth_threads() == max(1, min(n, _SYNTH_MAX_THREADS))

"""Streaming-population tests (paged store, cohort sampling, stream engine).

Pins the ISSUE-8 guarantees: ``shard(cid)`` purity in ``(seed, cid)``,
analytic histograms == synthesized data, LRU eviction/rehydration parity
with the eager store, cohort-draw determinism shared by every engine,
Pareto ``prate`` bias sanity, full-participation runs untouched by the
sampling layer, stream-vs-sync-vs-reference cohort trajectory parity, and
server-side momentum against the centralized SGD+momentum oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.core.hfl import HFLSchedule
from repro.data.shard_source import HealthShardSource
from repro.data.synthetic_health import make_dataset
from repro.engine import (
    AsyncHFLEngine,
    BatchedSyncEngine,
    DeviceShardStore,
    PagedShardStore,
    StreamSyncEngine,
)
from repro.federated import CohortSpec, HFLSimulation, build_scenario, pareto_weights
from repro.federated.sampling import _floyd_sample
from repro.federated.stream import edge_kld_uniform, striped_assignment
from repro.telemetry import Telemetry

M, N_EDGES = 120, 4
SCHEDULE = HFLSchedule(1, 1)


@pytest.fixture(scope="module")
def stream_sc():
    return build_scenario(
        "heartbeat", lazy=True, n_eus=M, n_edges=N_EDGES, seed=3,
        n_test_per_class=20,
    )


@pytest.fixture(scope="module")
def spec():
    return CohortSpec(size=24, seed=9)


@pytest.fixture(scope="module")
def stream_result(stream_sc, spec):
    return stream_sc.simulate(spec, cloud_rounds=3, schedule=SCHEDULE, seed=0)


@pytest.fixture(scope="module")
def materialized(stream_sc):
    """The same population as eager FLClient objects + dense assignment."""
    return list(stream_sc.clients()), stream_sc.assignment_matrix()


def _flat(tree) -> np.ndarray:
    return np.asarray(ravel_pytree(tree)[0])


# -- lazy source: purity and analytic exactness ----------------------------
def test_shard_source_pure_in_seed_and_cid():
    """shard(cid) is a pure function of (seed, cid): repeated calls and a
    fresh source instance synthesize bit-identical bytes — the property
    that makes eviction/rehydration and lazy==eager parity possible."""
    kw = dict(n_classes=4, length=32, channels=1, max_per_class=3, dom_boost=4)
    s1 = HealthShardSource(5, 50, **kw)
    s2 = HealthShardSource(5, 50, **kw)
    for cid in (0, 7, 49):
        a, b, c = s1.shard(cid), s1.shard(cid), s2.shard(cid)
        np.testing.assert_array_equal(a.x, b.x)
        np.testing.assert_array_equal(a.y, b.y)
        np.testing.assert_array_equal(a.x, c.x)
        np.testing.assert_array_equal(a.y, c.y)
    # a different seed is a different population
    other = HealthShardSource(6, 50, **kw).shard(7)
    assert not np.array_equal(other.x, s1.shard(7).x)


def test_analytic_counts_match_synthesized_shards(stream_sc):
    src = stream_sc.source
    sizes = src.sizes
    for cid in (0, 3, 57, M - 1):
        sh = src.shard(cid)
        assert len(sh) == sizes[cid]
        np.testing.assert_array_equal(
            np.bincount(sh.y, minlength=src.n_classes), src.class_counts_for(cid)
        )


def test_edge_histograms_exact(stream_sc):
    """The analytic (N, K) histograms equal a brute-force materialization."""
    src, eo = stream_sc.source, stream_sc.edge_of
    hist = np.zeros((N_EDGES, src.n_classes), np.int64)
    for cid in range(M):
        hist[eo[cid]] += np.bincount(src.shard(cid).y, minlength=src.n_classes)
    np.testing.assert_array_equal(hist, stream_sc.edge_class_counts)


def test_striped_assignment_minimizes_kld(stream_sc):
    """Striping dominant-class families round-robin beats the hash baseline
    on the paper's per-edge KLD-to-uniform objective (eq. 19)."""
    src = stream_sc.source
    hash_eo = striped_assignment(src, N_EDGES, strategy="hash")
    kld_hash = edge_kld_uniform(src.edge_histograms(hash_eo, N_EDGES))
    assert stream_sc.kld_total() <= kld_hash + 1e-9


# -- paged store -----------------------------------------------------------
def test_paged_store_matches_device_store_under_eviction(stream_sc):
    """Forced-eviction waves through a 6-slot store return the exact bytes
    the O(M) eager store holds — rehydration is invisible."""
    shards = stream_sc.source.materialize(range(16))
    dev = DeviceShardStore.from_shards(shards)
    paged = PagedShardStore.from_shards(shards, capacity=6)
    rng = np.random.default_rng(0)
    for _ in range(6):
        cids = np.sort(rng.choice(16, size=5, replace=False))
        idx = np.stack(
            [rng.integers(0, len(shards[c]), size=(2, 4)) for c in cids]
        )
        dx, dy = dev.gather(cids, idx)
        px, py = paged.gather(cids, idx)
        np.testing.assert_array_equal(np.asarray(dx), np.asarray(px))
        np.testing.assert_array_equal(np.asarray(dy), np.asarray(py))
    assert paged.evictions > 0  # the waves really did thrash the slab


def test_paged_store_lru_counters(stream_sc):
    shards = stream_sc.source.materialize(range(5))
    st = PagedShardStore.from_shards(shards, capacity=2)
    st.ensure([0, 1])
    assert (st.hits, st.misses, st.evictions) == (0, 2, 0)
    st.ensure([2])  # evicts 0 (LRU)
    st.ensure([1])  # hit: 1 still resident
    st.ensure([0])  # miss again: 0 was evicted; evicts 2
    st.ensure([3])  # evicts 1 (0 is MRU)
    st.ensure([0])  # hit: 0 survived
    assert (st.hits, st.misses, st.evictions) == (2, 5, 3)
    with pytest.raises(ValueError):
        st.ensure([0, 1, 2])  # cohort larger than the slab


# -- cohort sampling -------------------------------------------------------
def test_cohort_draw_deterministic_and_dense_sparse_parity():
    """Draws are pure in (seed, b, er); eligible=None (streaming fast path)
    equals the materialized arange(M) eligible list."""
    spec = CohortSpec(size=16, seed=5)
    a = spec.draw(2, 3, eligible=None, m=200)
    b = spec.draw(2, 3, eligible=None, m=200)
    c = spec.draw(2, 3, eligible=np.arange(200), m=200)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)
    assert len(a) == 16 == len(set(a.tolist()))
    assert np.all((a >= 0) & (a < 200)) and np.all(np.diff(a) > 0)
    assert not np.array_equal(a, spec.draw(2, 4, eligible=None, m=200))


def test_cohort_mask_matches_draw(stream_sc, spec):
    """mask() (dense engines) and draw() (streaming engine) agree on the
    same (b, er) key — the cross-engine determinism glue."""
    mask = spec.mask(1, 1, edge_of=stream_sc.edge_of)
    np.testing.assert_array_equal(
        np.flatnonzero(mask), spec.draw(1, 1, eligible=None, m=M)
    )


def test_floyd_sample_distinct_in_range():
    for n, k in ((10, 10), (100, 7), (1000, 999), (5, 1)):
        s = _floyd_sample(np.random.default_rng(n + k), n, k)
        assert len(s) == k == len(set(s.tolist()))
        assert np.all((s >= 0) & (s < n))


def test_prate_cohort_biased_toward_heavy_weights():
    """Pareto prate: high-weight clients are selected far more often than
    low-weight ones, and the weights themselves are pure in (seed, i)."""
    m, spec = 300, CohortSpec(size=30, strategy="prate", seed=11)
    w = pareto_weights(11, m, spec.alpha)
    np.testing.assert_array_equal(w, pareto_weights(11, m, spec.alpha))
    counts = np.zeros(m)
    for b in range(40):
        counts[spec.draw(b, 0, eligible=None, m=m)] += 1
    order = np.argsort(w)
    top, bot = counts[order[-30:]], counts[order[:30]]
    assert top.mean() > 1.5 * max(bot.mean(), 1e-9)


def test_per_edge_quota_near_equal(stream_sc):
    spec = CohortSpec(size=20, strategy="per_edge", seed=2)
    mem = spec.draw(0, 1, eligible=None, m=M, edge_of=stream_sc.edge_of)
    per = np.bincount(stream_sc.edge_of[mem], minlength=N_EDGES)
    assert per.sum() == 20
    assert per.max() - per.min() <= 1


def test_full_participation_cohort_is_identity():
    """A cohort covering the whole population selects everyone — and does
    so without consuming any RNG (the c == q early-return)."""
    full = CohortSpec(size=10_000, seed=1)
    np.testing.assert_array_equal(
        full.draw(0, 0, eligible=None, m=37), np.arange(37)
    )


def test_sampling_layer_leaves_full_runs_bit_identical():
    """cohort=None trajectories are byte-for-byte what they were before the
    sampling layer existed: side-channel draws consume no engine RNG, so
    interleaving them with a run changes nothing (golden seed pins live in
    test_consistency.py; this pins the no-cohort kwarg path)."""
    sc = build_scenario("heartbeat", scale=0.02, seed=0, n_test_per_class=10)
    lam = sc.assign("eara-sca").lam
    r1 = sc.simulate(lam, cloud_rounds=2, schedule=SCHEDULE, seed=0)
    # draw cohorts between the two runs — must not perturb anything
    side = CohortSpec(size=4, seed=0)
    for b in range(5):
        side.draw(b, 1, eligible=None, m=64)
    r2 = sc.simulate(lam, cloud_rounds=2, schedule=SCHEDULE, seed=0, cohort=None)
    assert [m.test_acc for m in r1.history] == [m.test_acc for m in r2.history]
    np.testing.assert_array_equal(_flat(r1.final_params), _flat(r2.final_params))


# -- engine parity on sampled rounds --------------------------------------
@pytest.fixture(scope="module", params=["heartbeat", "seizure"])
def cohort_population(request, stream_sc, spec, stream_result):
    """(lazy scenario, its 3-round stream run) for each health dataset: the
    paper's heartbeat CNN (1 x 187 in) and its seizure CNN (19 x 178 in)."""
    if request.param == "heartbeat":
        return stream_sc, stream_result
    sc = build_scenario(
        request.param, lazy=True, n_eus=M, n_edges=N_EDGES, seed=3,
        n_test_per_class=20,
    )
    return sc, sc.simulate(spec, cloud_rounds=3, schedule=SCHEDULE, seed=0)


def test_stream_matches_sync_engine_on_cohort_rounds(cohort_population, spec):
    """The streaming engine (lazy source + paged store + O(cohort) partial
    segment sums) tracks the materialized sync engine and the plain
    per-client oracle (``HFLSimulation``) on the same cohort draws:
    accuracies equal, parameters allclose (the partial-sum association
    order differs, so bit-identity is not expected)."""
    sc, stream_result = cohort_population
    clients, lam = list(sc.clients()), sc.assignment_matrix()
    for oracle in (BatchedSyncEngine, HFLSimulation):
        res = oracle(
            clients, lam, sc.program, sc.test,
            schedule=SCHEDULE, seed=0, cohort=spec,
        ).run(3)
        np.testing.assert_allclose(
            [m.test_acc for m in stream_result.history],
            [m.test_acc for m in res.history],
            atol=1e-6,
        )
        np.testing.assert_allclose(
            _flat(stream_result.final_params), _flat(res.final_params), atol=1e-4
        )


def test_reference_matches_sync_on_cohort_rounds(stream_sc, spec, materialized):
    clients, lam = materialized
    sim = HFLSimulation(
        clients, lam, stream_sc.program, stream_sc.test,
        schedule=SCHEDULE, seed=0, cohort=spec,
    )
    res_ref = sim.run(2)
    eng = BatchedSyncEngine(
        clients, lam, stream_sc.program, stream_sc.test,
        schedule=SCHEDULE, seed=0, cohort=spec,
    )
    res_sync = eng.run(2)
    np.testing.assert_allclose(
        [m.test_acc for m in res_ref.history],
        [m.test_acc for m in res_sync.history],
        atol=1e-6,
    )
    np.testing.assert_allclose(
        _flat(res_ref.final_params), _flat(res_sync.final_params), atol=1e-5
    )


def test_async_engine_cohort_runs_deterministic(stream_sc, spec, materialized):
    """The async engine accepts the same CohortSpec (drawn at edge-round
    key 1, the members sync sees) and its sampled runs are reproducible."""
    clients, lam = materialized
    lat = np.full((M, N_EDGES), 0.01)

    def go():
        eng = AsyncHFLEngine(
            clients, lam, stream_sc.program, stream_sc.test, lat,
            schedule=SCHEDULE, seed=0, cohort=spec,
        )
        return eng.run(2)

    a, b = go(), go()
    assert [m.test_acc for m in a.history] == [m.test_acc for m in b.history]
    np.testing.assert_array_equal(_flat(a.final_params), _flat(b.final_params))


def test_stream_paging_invisible_to_results(stream_sc, spec, stream_result):
    """A minimum-capacity paged store (slots == cohort size, heavy
    eviction) produces the bit-identical trajectory of the default run:
    rehydrated shards are the same bytes, so paging never shows up in
    results — only in the hit/miss/eviction counters."""
    eng = StreamSyncEngine(
        stream_sc.source, stream_sc.edge_of, stream_sc.program, stream_sc.test,
        cohort=spec, n_edges=N_EDGES, schedule=SCHEDULE, seed=0, page_slots=24,
    )
    res = eng.run(3)
    assert eng.store.evictions > 0
    assert [m.test_acc for m in res.history] == [
        m.test_acc for m in stream_result.history
    ]
    np.testing.assert_array_equal(
        _flat(res.final_params), _flat(stream_result.final_params)
    )


@pytest.fixture(scope="module")
def traced_stream(stream_sc, spec):
    """A stream run with telemetry on: ``run(3)`` then ``run(1)``, whose
    round replays the first call's cohort into a slab the second and third
    rounds have partly evicted, so it pages both hits and misses."""
    tel = Telemetry()
    eng = StreamSyncEngine(
        stream_sc.source, stream_sc.edge_of, stream_sc.program, stream_sc.test,
        cohort=spec, n_edges=N_EDGES, schedule=SCHEDULE, seed=0, telemetry=tel,
    )
    return eng, tel, [eng.run(3), eng.run(1)]


def test_stream_results_bit_identical_with_telemetry_on_and_off(
    stream_sc, spec, traced_stream
):
    eng = StreamSyncEngine(
        stream_sc.source, stream_sc.edge_of, stream_sc.program, stream_sc.test,
        cohort=spec, n_edges=N_EDGES, schedule=SCHEDULE, seed=0,
    )
    for on, off in zip(traced_stream[2], [eng.run(3), eng.run(1)]):
        assert off.telemetry is None and on.telemetry is not None
        assert [(m.test_acc, m.mean_local_loss) for m in on.history] == [
            (m.test_acc, m.mean_local_loss) for m in off.history
        ]
        np.testing.assert_array_equal(_flat(on.final_params), _flat(off.final_params))


def test_page_in_attributes_account_for_the_store(traced_stream):
    """Each ``cohort_epoch`` span carries its ``page_in`` span's figures:
    they sum to the store's counters, and the bytes are the padded miss
    batches' (rows padded to a power of two, floor 16, capped at the
    slab) that ``tel.upload`` counted."""
    eng, tel, _ = traced_stream
    spans = tel.tracer.spans
    epochs = [s.attrs for s in spans if s.name == "cohort_epoch"]
    pages = [s for s in spans if s.name == "page_in"]
    assert len(pages) == len(epochs) > 0
    st = eng.store
    assert st.hits > 0 and st.misses > 0
    assert sum(a["page_misses"] for a in epochs) == st.misses
    assert sum(a["page_hits"] for a in epochs) == st.hits
    assert sum(p.attrs["misses"] for p in pages) == st.misses
    row = st.n_max * int(np.prod(st._feat)) * 4 + st.n_max * 4 + 4  # x, y, slot id
    padded = [
        min(16 if k <= 16 else 1 << (k - 1).bit_length(), st.capacity) if k else 0
        for k in (p.attrs["misses"] for p in pages)
    ]
    uploaded = [p.attrs.get("h2d_bytes", 0) for p in pages]
    assert uploaded == [k * row for k in padded]
    assert sum(a["page_in_bytes"] for a in epochs) == sum(uploaded)
    assert sum(a["page_in_s"] for a in epochs) == pytest.approx(
        sum(p.duration for p in pages)
    )
    # the 24-client cohort's misses are synthesised inline, inside the span
    assert [p.attrs["workers"] for p in pages] == [
        1 if p.attrs["misses"] else 0 for p in pages
    ]
    assert all(0 <= p.attrs["synth_s"] <= p.duration for p in pages)
    assert all(p.attrs["synth_s"] > 0 for p in pages if p.attrs["misses"])
    assert sum(a["page_synth_s"] for a in epochs) == pytest.approx(
        sum(p.attrs["synth_s"] for p in pages)
    )


def test_lazy_lm_stream_matches_sync_engine():
    """End-to-end lazy LM (ISSUE 9 satellite): ``build_scenario(lazy=True,
    model="lm")`` — the ``TokenShardSource`` path, previously only
    health-tested — runs 2 rounds through ``StreamSyncEngine`` and tracks
    the materialized sync engine on the same cohort draws."""
    sc = build_scenario(
        "lm", lazy=True, model="lm", n_eus=24, n_edges=4, seed=1,
        n_test_per_class=20,
    )
    spec = CohortSpec(size=8, seed=7)
    res_stream = sc.simulate(spec, cloud_rounds=2, schedule=SCHEDULE, seed=0)
    assert len(res_stream.history) == 2
    assert all(np.isfinite(m.test_acc) for m in res_stream.history)
    clients, lam = list(sc.clients()), sc.assignment_matrix()
    eng = BatchedSyncEngine(
        clients, lam, sc.program, sc.test, schedule=SCHEDULE, seed=0,
        cohort=spec,
    )
    res_sync = eng.run(2)
    np.testing.assert_allclose(
        [m.test_acc for m in res_stream.history],
        [m.test_acc for m in res_sync.history],
        atol=1e-6,
    )
    np.testing.assert_allclose(
        _flat(res_stream.final_params), _flat(res_sync.final_params), atol=1e-4
    )


# -- server-side momentum --------------------------------------------------
def test_server_momentum_matches_centralized_sgd_oracle():
    """FedSGD + cloud momentum == centralized SGD with momentum.

    One client whose shard is exactly one batch, one edge: each round's
    aggregated delta is -lr * g, so the cloud's velocity recursion
    v <- mu v + delta must reproduce optimizers.sgd's vel <- mu vel + g,
    p <- p - lr vel step for step (up to float association)."""
    from repro.federated.client import FLClient
    from repro.federated.programs import CNNProgram, FedSGDProgram, as_program
    from repro.models.cnn1d import CNNConfig
    from repro.training.optimizers import sgd

    cfg = CNNConfig(in_channels=1, n_classes=3, seq_len=32, c1=4, c2=4, hidden=8)
    program = as_program(FedSGDProgram(base=CNNProgram(cfg), grad_bits=32))
    shard = make_dataset(
        np.random.default_rng(42), np.array([4, 3, 3]), length=32, channels=1
    )  # 10 samples == batch_size: the single FedSGD step sees the whole shard
    test = make_dataset(
        np.random.default_rng(43), np.array([5, 5, 5]), length=32, channels=1
    )
    lr, mu, rounds = 0.05, 0.9, 5
    client = FLClient(0, shard, program, batch_size=10, lr=lr)
    sim = HFLSimulation(
        [client], np.ones((1, 1), np.int8), program, test,
        schedule=SCHEDULE, seed=0, server_momentum=mu,
    )
    res = sim.run(rounds)

    params = program.init(jax.random.PRNGKey(0))
    opt = sgd(lr=lr, momentum=mu)
    state = opt.init(params)
    x, y = jnp.asarray(shard.x), jnp.asarray(shard.y)
    grad_fn = jax.grad(lambda p: program.loss(p, x, y))
    for step in range(rounds):
        params, state = opt.update(params, grad_fn(params), state, step)
    np.testing.assert_allclose(
        _flat(res.final_params), _flat(params), rtol=1e-4, atol=1e-6
    )

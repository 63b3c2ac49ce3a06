"""Batched/async engine tests: flatten round-trips, backend consistency,
sync-engine parity with the reference simulator, async straggler tolerance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compression import CompressionSpec
from repro.core.hfl import HFLSchedule
from repro.engine import (
    AsyncHFLEngine,
    BatchedSyncEngine,
    DeviceShardStore,
    EventQueue,
    FlatPack,
    flat_mean,
    flat_segment_mean,
)
from repro.engine.flatten import compress_flat_upload
from repro.federated import build_scenario
from repro.utils.tree import tree_ravel, tree_unravel


@pytest.fixture(scope="module")
def scenario():
    return build_scenario("heartbeat", scale=0.02, seed=0, n_test_per_class=20)


@pytest.fixture(scope="module")
def assignment(scenario):
    return scenario.assign("eara-sca").lam


# -- flatten ---------------------------------------------------------------
def _random_tree(key, shapes):
    keys = jax.random.split(key, len(shapes))
    return {f"p{i}": jax.random.normal(k, s) for i, (k, s) in enumerate(zip(keys, shapes))}


@pytest.mark.parametrize(
    "shapes",
    [
        [(3,)],
        [(2, 3), (4,), (1, 1, 5)],
        [(7, 2), (), (3, 3, 2)],
    ],
)
def test_ravel_unravel_round_trip(shapes):
    tree = _random_tree(jax.random.PRNGKey(len(shapes)), shapes)
    flat, spec = tree_ravel(tree)
    assert flat.shape == (sum(int(np.prod(s)) for s in shapes),)
    back = tree_unravel(spec, flat)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ravel_round_trip_property():
    """Property-style sweep: random structures, dtypes, nestings."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(
        st.lists(st.lists(st.integers(1, 4), min_size=0, max_size=3), min_size=1, max_size=4),
        st.integers(0, 2**31 - 1),
    )
    def check(shapes, seed):
        tree = _random_tree(jax.random.PRNGKey(seed), [tuple(s) for s in shapes])
        flat, spec = tree_ravel(tree)
        back = tree_unravel(spec, flat)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    check()


def test_flat_pack_stack_and_mean_consistency():
    """The pallas flat path and tree_weighted_mean are pinned together."""
    from repro.models.cnn1d import HEARTBEAT_CNN, cnn_init
    from repro.utils.tree import tree_weighted_mean

    trees = [cnn_init(jax.random.PRNGKey(i), HEARTBEAT_CNN) for i in range(5)]
    w = np.array([3.0, 1.0, 4.0, 1.0, 5.0], np.float32)
    pack = FlatPack(trees[0])
    mat = pack.stack(trees)
    assert mat.shape == (5, pack.dim)
    ref = pack.ravel(tree_weighted_mean(trees, w))
    for backend in ("pallas", "reference"):
        out = flat_mean(mat, w, backend=backend)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_unravel_rejects_wrong_size():
    tree = {"a": jnp.zeros((3,))}
    _, spec = tree_ravel(tree)
    with pytest.raises(ValueError):
        tree_unravel(spec, jnp.zeros((5,)))


def test_flat_segment_mean_backends_agree():
    """pallas (kernel off-TPU routes to interpret/segment_sum) vs reference."""
    u = jax.random.normal(jax.random.PRNGKey(0), (9, 301))
    seg = np.array([0, 0, 1, 1, 1, 3, 3, 3, 3])
    w = np.linspace(0.5, 2.0, 9).astype(np.float32)
    outs = [
        np.asarray(flat_segment_mean(u, seg, w, 4, backend=b))
        for b in ("pallas", "reference")
    ]
    kern = np.asarray(flat_segment_mean(u, seg, w, 4, interpret=True))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)
    np.testing.assert_allclose(kern, outs[1], atol=1e-5)
    np.testing.assert_array_equal(outs[0][2], 0.0)  # empty segment


# -- device shard store ----------------------------------------------------
def test_device_shard_store_gather_matches_numpy(scenario):
    store = DeviceShardStore(scenario.clients)
    rng = np.random.default_rng(0)
    cids = np.array([i for i, c in enumerate(scenario.clients) if len(c.shard)][:4])
    idx = np.stack(
        [rng.integers(0, len(scenario.clients[i].shard), (2, 3)) for i in cids]
    )
    xb, yb = store.gather(cids, idx)
    assert xb.shape == (len(cids), 2, 3) + scenario.clients[0].shard.x.shape[1:]
    for k, i in enumerate(cids):
        np.testing.assert_array_equal(
            np.asarray(xb[k]), scenario.clients[i].shard.x[idx[k]]
        )
        np.testing.assert_array_equal(
            np.asarray(yb[k]), scenario.clients[i].shard.y[idx[k]]
        )


# -- event queue -----------------------------------------------------------
def test_event_queue_deterministic_order():
    q = EventQueue()
    q.push(2.0, "b")
    q.push(1.0, "a")
    q.push(1.0, "c")  # same time: FIFO by seq
    kinds = [q.pop().kind for _ in range(3)]
    assert kinds == ["a", "c", "b"]
    assert q.now == 2.0
    with pytest.raises(ValueError):
        q.push(1.0, "late")


# -- sync parity -----------------------------------------------------------
@pytest.mark.parametrize("schedule", [HFLSchedule(1, 1), HFLSchedule(2, 2)])
def test_sync_engine_matches_reference(scenario, assignment, schedule):
    """Fixed seed, upp=1.0: the batched engine must reproduce the reference
    simulator's final accuracy within 1e-6 (bit-exact with backend=reference)."""
    sc = scenario
    ref = sc.simulate(assignment, cloud_rounds=2, schedule=schedule, seed=0, upp=1.0)
    for backend in ("reference", "pallas"):
        eng = sc.simulate(
            assignment, cloud_rounds=2, schedule=schedule, seed=0, upp=1.0,
            engine="sync", backend=backend,
        )
        for mr, me in zip(ref.history, eng.history):
            assert me.test_acc == pytest.approx(mr.test_acc, abs=1e-6)
            # loss is continuous, so it shows the ~1e-3 param drift that the
            # quantized accuracy metric does not
            assert me.mean_local_loss == pytest.approx(mr.mean_local_loss, abs=5e-3)
        assert eng.final_accuracy() == pytest.approx(ref.final_accuracy(), abs=1e-6)
        assert eng.accountant.edge_rounds == ref.accountant.edge_rounds
        assert eng.accountant.cloud_rounds == ref.accountant.cloud_rounds
        assert eng.accountant.eu_traffic_bits() == ref.accountant.eu_traffic_bits()
    # param trajectories track closely: the cohort path computes identical
    # per-client math, but the batched conv backward accumulates in a
    # different order (1-ulp/step), which Adam's early sqrt-normalized
    # updates amplify to ~1e-3 over multi-step schedules
    eng = sc.simulate(
        assignment, cloud_rounds=2, schedule=schedule, seed=0, upp=1.0,
        engine="sync", backend="reference",
    )
    for a, b in zip(jax.tree.leaves(ref.final_params), jax.tree.leaves(eng.final_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)


@pytest.mark.parametrize("schedule,upp", [(HFLSchedule(1, 1), 1.0), (HFLSchedule(2, 2), 0.6)])
def test_sync_engine_device_pipeline_matches_host(scenario, assignment, schedule, upp):
    """The device-resident round and the reference simulator consume the
    same RNG stream and must produce the same trajectory on seed 11, under
    full and partial participation (segment aggregation reassociates the
    FedAvg sums, so params agree to float tolerance, accuracy to 1e-6).
    The name is kept from when the sync engine had a host pipeline to
    compare with."""
    ref = scenario.simulate(assignment, cloud_rounds=2, schedule=schedule, seed=11, upp=upp)
    dev = scenario.simulate(
        assignment, cloud_rounds=2, schedule=schedule, seed=11, upp=upp,
        engine="sync",
    )
    for mr, md in zip(ref.history, dev.history):
        assert md.test_acc == pytest.approx(mr.test_acc, abs=1e-6)
        assert md.mean_local_loss == pytest.approx(mr.mean_local_loss, abs=5e-3)
    assert dev.accountant.eu_traffic_bits() == ref.accountant.eu_traffic_bits()
    for a, b in zip(jax.tree.leaves(ref.final_params), jax.tree.leaves(dev.final_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)


def test_sync_engine_device_pipeline_dual_connectivity(scenario):
    """DCA rows (clients on 2 edges) exercise the segment-mean start path;
    the engine and the reference simulator must agree."""
    m = len(scenario.clients)
    n = scenario.n_edges
    asn = np.zeros((m, n))
    asn[np.arange(m), np.arange(m) % n] = 1.0
    asn[: m // 2, (np.arange(m // 2) + 1) % n] = 1.0  # half the EUs dual-homed
    ref = scenario.simulate(asn, cloud_rounds=1, seed=5, upp=1.0)
    eng = scenario.simulate(asn, cloud_rounds=1, seed=5, upp=1.0, engine="sync")
    assert eng.final_accuracy() == pytest.approx(ref.final_accuracy(), abs=1e-6)


def test_sync_engine_matches_reference_with_upp(scenario, assignment):
    """Partial participation draws the same RNG stream in both simulators."""
    ref = scenario.simulate(assignment, cloud_rounds=2, seed=3, upp=0.6)
    eng = scenario.simulate(
        assignment, cloud_rounds=2, seed=3, upp=0.6, engine="sync", backend="reference"
    )
    for mr, me in zip(ref.history, eng.history):
        assert me.test_acc == pytest.approx(mr.test_acc, abs=1e-6)


# -- compression wiring ----------------------------------------------------
@pytest.mark.parametrize("engine", ["reference", "sync"])
def test_compression_reduces_accounted_traffic(scenario, assignment, engine):
    spec = CompressionSpec("topk", fraction=0.05)
    dense = scenario.simulate(assignment, cloud_rounds=1, seed=0, engine=engine)
    comp = scenario.simulate(
        assignment, cloud_rounds=1, seed=0, engine=engine, compression=spec
    )
    up_dense = sum(dense.accountant.eu_bits_up.values())
    up_comp = sum(comp.accountant.eu_bits_up.values())
    assert up_comp < 0.2 * up_dense  # ~5% of values + indices
    # downlink (model broadcast) unchanged
    assert sum(comp.accountant.eu_bits_down.values()) == pytest.approx(
        sum(dense.accountant.eu_bits_down.values())
    )
    # training still works on compressed uploads
    assert comp.final_accuracy() > 1.0 / 5


def test_compress_flat_upload_error_feedback_accumulates():
    """Over 3 rounds the transmitted total plus the residual error equals
    the uncompressed delta total — error feedback loses nothing."""
    spec = CompressionSpec("topk", fraction=0.2)
    rng = np.random.default_rng(0)
    d = 40
    errors = {}
    sent_total = np.zeros(d)
    delta_total = np.zeros(d)
    start = jnp.zeros((d,), jnp.float32)
    for _ in range(3):
        delta = rng.normal(size=d).astype(np.float32)
        trained = start + jnp.asarray(delta)
        up = compress_flat_upload(spec, errors, 7, start, trained)
        sent = np.asarray(up - start)
        # each round ships exactly k = ceil(0.2 * 40) = 8 values
        assert int(np.count_nonzero(sent)) == 8
        sent_total += sent
        delta_total += delta
        start = trained  # next round trains from the uncompressed model
    residual = np.asarray(errors[7])
    np.testing.assert_allclose(sent_total + residual, delta_total, atol=1e-5)


def test_compress_flat_upload_errors_are_per_client():
    """errors dict keys one state per client; streams do not interfere."""
    spec = CompressionSpec("topk", fraction=0.1)
    rng = np.random.default_rng(1)
    errors = {}
    start = jnp.zeros((30,), jnp.float32)
    d0 = jnp.asarray(rng.normal(size=30).astype(np.float32))
    d1 = jnp.asarray(rng.normal(size=30).astype(np.float32))
    compress_flat_upload(spec, errors, 0, start, start + d0)
    compress_flat_upload(spec, errors, 1, start, start + d1)
    assert set(errors) == {0, 1}
    assert not np.allclose(np.asarray(errors[0]), np.asarray(errors[1]))
    # a solo-client run from the same start produces the same state for 0
    solo = {}
    compress_flat_upload(spec, solo, 0, start, start + d0)
    np.testing.assert_allclose(np.asarray(solo[0]), np.asarray(errors[0]), atol=1e-7)


def test_topk_exact_k_under_ties():
    """Repeated magnitudes at the threshold must not inflate the payload."""
    from repro.core.compression import topk_sparsify

    tree = {"w": jnp.ones((10, 10))}  # all-tied magnitudes
    sparse, err = topk_sparsify(tree, 0.1)
    assert int(np.count_nonzero(np.asarray(sparse["w"]))) == 10
    np.testing.assert_allclose(
        np.asarray(sparse["w"] + err["w"]), np.asarray(tree["w"]), rtol=1e-6
    )


# -- async -----------------------------------------------------------------
def test_async_straggler_does_not_block(scenario, assignment):
    """One EU is 3 orders of magnitude slower; quorum aggregation must close
    edge rounds (and the cloud round) without waiting for it."""
    sc = scenario
    lat = np.full(sc.cost.latency.shape, 0.01)
    straggler = int(np.argmax(assignment.sum(1) > 0))
    lat[straggler, :] = 50.0
    eng = AsyncHFLEngine(
        sc.clients, assignment, sc.cfg, sc.test, latency=lat,
        schedule=HFLSchedule(1, 2), seed=0, quorum=0.5, staleness_decay=0.5,
    )
    res = eng.run(2)
    assert len(res.history) == 2
    assert res.wall_seconds < 50.0  # did not wait for the straggler
    assert res.accountant.cloud_rounds == 2
    assert res.accountant.edge_rounds >= 2
    assert res.final_accuracy() > 1.0 / 5


def test_async_sync_corner_matches_fedavg_semantics(scenario, assignment):
    """quorum=1, decay=1: every edge waits for all EUs -> plain FedAvg per
    round; final accuracy should land near the sync engine's."""
    sc = scenario
    ref = sc.simulate(assignment, cloud_rounds=1, seed=0, upp=1.0)
    eng = sc.simulate(
        assignment, cloud_rounds=1, seed=0, upp=1.0,
        engine="async", quorum=1.0, staleness_decay=1.0, backend="reference",
    )
    assert eng.final_accuracy() == pytest.approx(ref.final_accuracy(), abs=1e-6)
    assert eng.wall_seconds > 0


def test_async_via_scenario_knob(scenario, assignment):
    res = scenario.simulate(
        assignment, cloud_rounds=1, seed=0, engine="async", quorum=0.75
    )
    assert len(res.history) == 1
    assert res.wall_seconds > 0


def test_unknown_engine_raises(scenario, assignment):
    with pytest.raises(ValueError):
        scenario.simulate(assignment, cloud_rounds=1, engine="nope")

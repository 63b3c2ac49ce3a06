"""Engine edge-case tests (ISSUE 5 satellite): previously untested corners
of ``sync_sim`` / ``async_sim`` — single-client edges, quorum=1 dispatch
cadence, empty secondary-edge DCA columns, and FedSGD grad_bits=16 under an
explicit CompressionSpec (the spec must take precedence)."""
import numpy as np
import pytest

from repro.core.compression import CompressionSpec
from repro.core.hfl import HFLSchedule
from repro.engine import AsyncHFLEngine, BatchedSyncEngine
from repro.data.synthetic_health import Dataset
from repro.federated import build_scenario
from repro.federated.client import FLClient
from repro.federated.programs import FedSGDProgram, MLPProgram
from repro.federated.simulation import HFLSimulation


@pytest.fixture(scope="module")
def scenario():
    return build_scenario("heartbeat", scale=0.02, seed=0, n_test_per_class=10)


def _single_client_edge_assignment(m, n):
    """Edge 0 serves exactly ONE client; the rest round-robin over 1..n-1."""
    asn = np.zeros((m, n))
    asn[0, 0] = 1.0
    asn[np.arange(1, m), 1 + np.arange(m - 1) % (n - 1)] = 1.0
    return asn


def test_single_client_edge_matches_reference(scenario):
    """An edge with one member degenerates FedAvg to that client's upload;
    the sync engine must still track the reference exactly."""
    m, n = len(scenario.clients), scenario.n_edges
    asn = _single_client_edge_assignment(m, n)
    ref = scenario.simulate(asn, cloud_rounds=2, seed=2, upp=1.0)
    eng = scenario.simulate(asn, cloud_rounds=2, seed=2, upp=1.0, engine="sync")
    for mr, me in zip(ref.history, eng.history):
        assert me.test_acc == pytest.approx(mr.test_acc, abs=1e-6)
        assert me.mean_local_loss == pytest.approx(mr.mean_local_loss, abs=5e-3)


def test_async_quorum_one_aggregates_per_upload(scenario):
    """quorum -> one reporter: every single upload flushes the edge, so the
    edge-round count equals what per-upload aggregation implies, and the
    run still converges to a sane model."""
    m, n = len(scenario.clients), scenario.n_edges
    asn = np.zeros((m, n))
    asn[np.arange(m), np.arange(m) % n] = 1.0
    lat = np.full((m, n), 0.01)
    eng = AsyncHFLEngine(
        scenario.clients, asn, scenario.program, scenario.test, latency=lat,
        schedule=HFLSchedule(1, 2), seed=0, quorum=1e-9, staleness_decay=1.0,
    )
    res = eng.run(1)
    # every edge needs edge_per_cloud=2 flushes; each flush consumed ONE
    # upload because the quorum count floors at a single reporter
    assert res.accountant.edge_rounds == 2 * n
    assert len(res.history) == 1
    assert np.isfinite(res.history[0].mean_local_loss)


def test_empty_secondary_edge_dca_membership(scenario):
    """A DCA population where one edge column is entirely EMPTY: the empty
    edge must keep (and report) the global model, not poison the cloud
    mean with zeros, and all engines must agree with the reference."""
    m, n = len(scenario.clients), scenario.n_edges
    asn = np.zeros((m, n))
    asn[np.arange(m), np.arange(m) % (n - 1)] = 1.0  # edge n-1 never assigned
    asn[: m // 2, 0] = 1.0  # plus some dual-connectivity rows
    ref = scenario.simulate(asn, cloud_rounds=1, seed=4, upp=1.0)
    eng = scenario.simulate(asn, cloud_rounds=1, seed=4, upp=1.0, engine="sync")
    assert eng.final_accuracy() == pytest.approx(ref.final_accuracy(), abs=1e-6)
    lat = np.full((m, n), 0.01)
    asy = AsyncHFLEngine(
        scenario.clients, asn, scenario.program, scenario.test, latency=lat,
        seed=4, quorum=1.0, staleness_decay=1.0,
    )
    res = asy.run(1)
    assert len(res.history) == 1
    assert np.isfinite(res.history[0].mean_local_loss)


def _fedsgd_population():
    rng = np.random.default_rng(0)
    program = FedSGDProgram(
        base=MLPProgram(feat=(8, 1), classes=2, hidden=4), grad_bits=16
    )
    clients = []
    for i in range(4):
        n = 6 + i
        shard = Dataset(rng.normal(size=(n, 8, 1)).astype(np.float32),
                        rng.integers(0, 2, n).astype(np.int32), 2)
        clients.append(FLClient(i, shard, program))
    test = Dataset(rng.normal(size=(8, 8, 1)).astype(np.float32),
                   rng.integers(0, 2, 8).astype(np.int32), 2)
    asn = np.zeros((4, 2))
    asn[np.arange(4), np.arange(4) % 2] = 1.0
    return program, clients, test, asn


def test_fedsgd16_under_compression_spec_takes_precedence():
    """grad_bits=16 AND an explicit CompressionSpec: the spec wins — the
    uplink is charged at the spec's bits (not half the model), the fp16
    cast is NOT applied (error-feedback compression transforms the delta
    instead), and engine/reference accounting agree."""
    program, clients, test, asn = _fedsgd_population()
    spec = CompressionSpec("topk", fraction=0.25)
    ref = HFLSimulation(clients, asn, program, test, seed=0, compression=spec)
    r_ref = ref.run(2)
    eng = BatchedSyncEngine(
        clients, asn, program, test, seed=0, compression=spec
    )
    r_eng = eng.run(2)
    import jax
    import jax.numpy as jnp

    from repro.engine import FlatPack

    model_bits = eng.accountant.model_bits
    dim = FlatPack(program.init(jax.random.PRNGKey(0))).dim
    spec_bits = spec.bits(jnp.zeros((dim,), jnp.float32))
    for i in range(len(clients)):
        up = eng.accountant.eu_bits_up[i]
        assert up != pytest.approx(2 * model_bits * 0.5)  # NOT the fp16 payload
        assert up == pytest.approx(2 * spec_bits)  # the spec's price, per round
        # engine bits come from the flat (D,) layout, reference from the
        # per-leaf tree; topk fractions round per leaf, so allow 20%
        assert up == pytest.approx(r_ref.accountant.eu_bits_up[i], rel=0.2)
    # trajectories DIVERGE by design (global vs per-leaf top-k select
    # different entries) but both must stay finite and trainable
    for m in list(r_ref.history) + list(r_eng.history):
        assert np.isfinite(m.mean_local_loss)
        assert 0.0 <= m.test_acc <= 1.0

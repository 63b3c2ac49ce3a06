"""Distillation aggregation layer (ISSUE 5): heterogeneous-model federation.

Pins the tentpole guarantees: the flat (engine) fuse matches the tree
(reference) fuse to 1e-5, `build_scenario(model_mix=...)` trains 2+ cloud
rounds with finite loss on all three engines, homogeneous populations are
untouched (bit-identical to `model=`), and the group-aware plumbing
(cohort blocks, per-group accounting, public shard store) behaves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hfl import HFLSchedule
from repro.data.synthetic_health import Dataset
from repro.engine import BatchedSyncEngine, DeviceShardStore, FlatPack, LocalJob, run_cohorts
from repro.engine.distill import (
    DistillSpec,
    check_distillable,
    distill_edge,
    distill_fuse_flat,
    kd_loss,
    soft_targets,
)
from repro.federated import build_scenario
from repro.federated.client import FLClient
from repro.federated.programs import (
    CNNProgram,
    FedSGDProgram,
    LMProgram,
    MLPProgram,
    group_clients,
)
from repro.federated.simulation import HeteroHFLSimulation
from repro.models.cnn1d import CNNConfig

MICRO_CNN = CNNConfig(in_channels=1, n_classes=3, seq_len=16, c1=4, c2=4, hidden=8)


def _micro_programs():
    return (
        CNNProgram(MICRO_CNN),
        MLPProgram(feat=(MICRO_CNN.seq_len, MICRO_CNN.in_channels), classes=3, hidden=4),
    )


@pytest.fixture(scope="module")
def mix_scenario():
    return build_scenario(
        "heartbeat", model_mix={"cnn": 12, "mlp": 6}, scale=0.02, seed=0,
        n_test_per_class=10,
    )


@pytest.fixture(scope="module")
def mix_assignment(mix_scenario):
    return mix_scenario.assign("eara-sca").lam


# -- program hooks -----------------------------------------------------------
def test_apply_logits_defaults_to_apply_and_fedsgd_delegates():
    cnn, mlp = _micro_programs()
    params = mlp.init(jax.random.PRNGKey(0))
    x = jnp.zeros((2,) + mlp.feat_shape, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(mlp.apply_logits(params, x)), np.asarray(mlp.apply(params, x))
    )
    wrapped = FedSGDProgram(base=mlp)
    np.testing.assert_array_equal(
        np.asarray(wrapped.apply_logits(params, x)), np.asarray(mlp.apply(params, x))
    )


def test_distill_spec_and_compatibility_validation():
    with pytest.raises(ValueError):
        DistillSpec(steps=0)
    with pytest.raises(ValueError):
        DistillSpec(batch=0)
    with pytest.raises(ValueError):
        DistillSpec(temperature=0.0)
    cnn, mlp = _micro_programs()
    check_distillable([cnn, mlp])  # shared alphabet + layout: fine
    with pytest.raises(ValueError):  # label alphabets differ
        check_distillable([cnn, MLPProgram(feat=(16, 1), classes=5)])
    with pytest.raises(ValueError):  # shard layouts differ
        check_distillable([cnn, LMProgram()])


def test_group_clients_partitions_by_program_value():
    cnn, mlp = _micro_programs()
    rng = np.random.default_rng(0)
    shard = Dataset(rng.normal(size=(3, 16, 1)).astype(np.float32),
                    np.zeros(3, np.int32), 3)
    # equal-by-value programs share a group even as distinct objects
    clients = [FLClient(0, shard, CNNProgram(MICRO_CNN)), FLClient(1, shard, mlp),
               FLClient(2, shard, cnn)]
    programs, group_of = group_clients(clients)
    assert [p.name for p in programs] == ["cnn", "mlp"]
    np.testing.assert_array_equal(group_of, [0, 1, 0])
    assert clients[0].program_name == "cnn"


# -- the fuse itself ---------------------------------------------------------
def _random_edge_state(seed, n_edges=3):
    """Per-group (E, D_g) matrices of slightly-perturbed inits."""
    programs = _micro_programs()
    packs = [FlatPack(p.init(jax.random.PRNGKey(0))) for p in programs]
    key = jax.random.PRNGKey(seed)
    mats = []
    for g, (prog, pack) in enumerate(zip(programs, packs)):
        rows = []
        for j in range(n_edges):
            k = jax.random.fold_in(key, g * 17 + j)
            rows.append(pack.ravel(prog.init(k)))
        mats.append(jnp.stack(rows))
    return programs, packs, mats


def test_fuse_flat_matches_tree_reference():
    """Acceptance pin: the engines' vmapped flat fuse reproduces the
    reference tree fuse within 1e-5 on identical inputs."""
    programs, packs, mats = _random_edge_state(seed=1, n_edges=3)
    spec = DistillSpec(steps=3, batch=5, temperature=2.0, lr=1e-2)
    rng = np.random.default_rng(7)
    xb = rng.normal(size=(3, spec.steps, spec.batch, 16, 1)).astype(np.float32)
    fused_flat, _ = distill_fuse_flat(
        programs, [pk.spec for pk in packs], mats, xb, spec
    )
    for j in range(3):
        fused_tree, _ = distill_edge(
            programs, [pk.unravel(m[j]) for pk, m in zip(packs, mats)], xb[j], spec
        )
        for g, pk in enumerate(packs):
            np.testing.assert_allclose(
                np.asarray(fused_flat[g][j]), np.asarray(pk.ravel(fused_tree[g])),
                atol=1e-5,
            )


def test_fuse_reduces_kd_loss():
    """Students move toward the ensemble: KD loss after the fuse is lower
    than before on the SAME public batch."""
    programs, packs, mats = _random_edge_state(seed=2, n_edges=1)
    spec = DistillSpec(steps=8, batch=16, lr=5e-2)
    rng = np.random.default_rng(3)
    xb = rng.normal(size=(1, spec.steps, spec.batch, 16, 1)).astype(np.float32)
    x0 = jnp.asarray(xb[0, 0])
    before_params = [pk.unravel(m[0]) for pk, m in zip(packs, mats)]
    targets = soft_targets(programs, before_params, x0, spec.temperature)
    fused, _ = distill_fuse_flat(programs, [pk.spec for pk in packs], mats, xb, spec)
    for g, (prog, pk) in enumerate(zip(programs, packs)):
        before = float(kd_loss(prog, before_params[g], x0, targets, spec))
        after = float(kd_loss(prog, pk.unravel(fused[g][0]), x0, targets, spec))
        assert after < before


# -- scenario wiring ---------------------------------------------------------
def test_model_mix_scenario_wiring(mix_scenario):
    sc = mix_scenario
    assert sc.is_hetero
    assert sc.name == "heartbeat-mix(cnn+mlp)"
    assert [c.program_name for c in sc.clients] == ["cnn"] * 12 + ["mlp"] * 6
    assert sc.public is not None and len(sc.public) == sc.n_edges
    assert all(len(p) > 0 for p in sc.public)
    assert isinstance(sc.distill, DistillSpec)


def test_model_mix_validation():
    with pytest.raises(ValueError):  # counts must sum to the population
        build_scenario("heartbeat", model_mix={"cnn": 3, "mlp": 3}, scale=0.02)
    with pytest.raises(ValueError):  # unknown program name
        build_scenario("heartbeat", model_mix={"cnn": 17, "nope": 1}, scale=0.02)
    with pytest.raises(ValueError):  # families cannot cross
        build_scenario("heartbeat", model_mix={"cnn": 17, "lm": 1}, scale=0.02)
    with pytest.raises(ValueError):  # fedsgd + mix unsupported
        build_scenario("heartbeat", model_mix={"cnn": 18}, fedsgd=True, scale=0.02)
    with pytest.raises(ValueError):  # model= and model_mix= conflict
        build_scenario("heartbeat", model="mlp", model_mix={"cnn": 12, "mlp": 6},
                       scale=0.02)
    with pytest.raises(ValueError):  # health mix cannot ride the lm dataset
        build_scenario("lm", model_mix={"cnn": 12, "mlp": 6}, scale=0.02)


def test_homogeneous_model_mix_bit_identical_to_model():
    """A single-entry mix is NOT a hetero population: no public pool is
    drawn, no fuse runs, and the trajectory is bit-identical to model=."""
    kw = dict(scale=0.02, seed=0, n_test_per_class=10)
    a = build_scenario("heartbeat", model="mlp", **kw)
    b = build_scenario("heartbeat", model_mix={"mlp": 18}, **kw)
    assert not b.is_hetero and b.public is None and b.distill is None
    asn = a.assign("eara-sca").lam
    ra = a.simulate(asn, cloud_rounds=1, seed=3, engine="sync")
    rb = b.simulate(asn, cloud_rounds=1, seed=3, engine="sync")
    for la, lb in zip(jax.tree.leaves(ra.final_params), jax.tree.leaves(rb.final_params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# -- end-to-end: 2 cloud rounds on every engine ------------------------------
def test_mixed_two_rounds_all_engines(mix_scenario, mix_assignment):
    """Acceptance: model_mix trains 2+ cloud rounds with FINITE loss on
    the sync AND async engines, and the final params carry one tree per
    architecture."""
    for engine in ("sync", "async"):
        res = mix_scenario.simulate(mix_assignment, cloud_rounds=2, seed=0, engine=engine)
        assert len(res.history) == 2
        for m in res.history:
            assert np.isfinite(m.mean_local_loss)
            assert 0.0 <= m.test_acc <= 1.0
        assert set(res.final_params) == {"cnn", "mlp"}


def test_mixed_engine_matches_reference(mix_scenario, mix_assignment):
    """The sync engine reproduces the hetero reference simulator's
    trajectory (the reference trains each client with its own program and
    fuses with the tree-form distillation — this parity is the end-to-end
    correctness guarantee for the group-aware engine paths)."""
    ref = mix_scenario.simulate(
        mix_assignment, cloud_rounds=2, schedule=HFLSchedule(2, 1), seed=0
    )
    eng = mix_scenario.simulate(
        mix_assignment, cloud_rounds=2, schedule=HFLSchedule(2, 1), seed=0,
        engine="sync",
    )
    for mr, me in zip(ref.history, eng.history):
        assert me.test_acc == pytest.approx(mr.test_acc, abs=1e-6)
        assert me.mean_local_loss == pytest.approx(mr.mean_local_loss, abs=5e-3)
    assert eng.accountant.eu_bits_up == pytest.approx(ref.accountant.eu_bits_up)
    assert eng.accountant.eu_bits_down == pytest.approx(ref.accountant.eu_bits_down)
    assert eng.accountant.edge_rounds == ref.accountant.edge_rounds
    assert eng.accountant.edge_cloud_bits == pytest.approx(ref.accountant.edge_cloud_bits)


def test_mixed_accounting_per_group(mix_scenario, mix_assignment):
    """Each EU pays ITS architecture's payload: cnn clients the CNN model
    bits, mlp clients the (much smaller) MLP bits — up and down."""
    res = mix_scenario.simulate(
        mix_assignment, cloud_rounds=1, seed=0, engine="sync"
    )
    programs, group_of = group_clients(mix_scenario.clients)
    from repro.utils.tree import tree_size_bytes

    bits = [tree_size_bytes(p.init(jax.random.PRNGKey(0))) * 8 for p in programs]
    assert bits[0] != bits[1]  # the point of capability skew
    for i, c in enumerate(mix_scenario.clients):
        assert res.accountant.eu_bits_up[i] == pytest.approx(bits[group_of[i]])


def test_mixed_async_charges_group_payloads(mix_scenario, mix_assignment):
    res = mix_scenario.simulate(
        mix_assignment, cloud_rounds=1, seed=0, engine="async",
        quorum=1.0, staleness_decay=1.0,
    )
    sync = mix_scenario.simulate(
        mix_assignment, cloud_rounds=1, seed=0, engine="sync"
    )
    assert res.accountant.eu_bits_up == pytest.approx(sync.accountant.eu_bits_up)
    assert res.accountant.eu_bits_down == pytest.approx(sync.accountant.eu_bits_down)


def test_hetero_requires_public_shards(mix_scenario, mix_assignment):
    sc = mix_scenario
    with pytest.raises(ValueError):
        HeteroHFLSimulation(
            sc.clients, mix_assignment, sc.test, public=None, distill=DistillSpec()
        )
    with pytest.raises(ValueError):
        BatchedSyncEngine(
            sc.clients, mix_assignment, sc.program, sc.test,
            public_shards=None, distill=DistillSpec(),
        )


def test_mixed_without_distill_runs_independent_groups(mix_scenario, mix_assignment):
    """distill=None is a valid hetero federation (no knowledge transfer):
    groups evolve independently but everything still runs."""
    sim = HeteroHFLSimulation(
        mix_scenario.clients, mix_assignment, mix_scenario.test, seed=0
    )
    assert sim.distill is None
    res = sim.run(1)
    assert len(res.history) == 1 and np.isfinite(res.history[0].mean_local_loss)


# -- group-aware plumbing ----------------------------------------------------
def test_run_cohorts_mixed_blocks_bit_identical_to_solo():
    """Mixed-program job batches produce BIT-identical rows to running each
    architecture alone, and cross-block gathers are refused."""
    cnn, mlp = _micro_programs()
    rng = np.random.default_rng(0)
    shard = Dataset(rng.normal(size=(8, 16, 1)).astype(np.float32),
                    rng.integers(0, 3, 8).astype(np.int32), 3)
    clients = [FLClient(i, shard, p) for i, p in enumerate([cnn, mlp, cnn, mlp])]
    packs = {p: FlatPack(p.init(jax.random.PRNGKey(0))) for p in (cnn, mlp)}
    starts = {p: pk.ravel(p.init(jax.random.PRNGKey(1))) for p, pk in packs.items()}

    def jobs_for(cs):
        return [
            LocalJob(
                c, starts[c.program],
                [np.random.default_rng(100 + c.cid).integers(0, 8, (1, 10))],
                steps=1,
            )
            for c in cs
        ]

    mixed = run_cohorts(jobs_for(clients), cnn, packs[cnn])
    assert len(mixed.blocks) == 2
    solo_cnn = run_cohorts(jobs_for([clients[0], clients[2]]), cnn, packs[cnn])
    solo_mlp = run_cohorts(jobs_for([clients[1], clients[3]]), mlp, packs[mlp])
    for c, solo in [(clients[0], solo_cnn), (clients[2], solo_cnn),
                    (clients[1], solo_mlp), (clients[3], solo_mlp)]:
        np.testing.assert_array_equal(
            np.asarray(mixed.row(c.cid)), np.asarray(solo.row(c.cid))
        )
    with pytest.raises(ValueError):
        mixed.gather([0, 1])  # spans architecture blocks
    with pytest.raises(ValueError):
        mixed.matrix  # no single-matrix view of a mixed result


def test_store_from_shards_gather_matches_numpy():
    rng = np.random.default_rng(0)
    shards = [
        Dataset(rng.normal(size=(n, 6, 1)).astype(np.float32),
                rng.integers(0, 2, n).astype(np.int32), 2)
        for n in (3, 5, 2)
    ]
    store = DeviceShardStore.from_shards(shards)
    idx = np.stack([rng.integers(0, len(s), (2, 4)) for s in shards])
    xb, yb = store.gather(np.arange(3), idx)
    for j, s in enumerate(shards):
        np.testing.assert_array_equal(np.asarray(xb[j]), s.x[idx[j]])
        np.testing.assert_array_equal(np.asarray(yb[j]), s.y[idx[j]])


@pytest.mark.slow
def test_sequence_model_mix_smoke():
    """lm+moe capability mix on one token population: one cloud round,
    finite loss, per-group final params."""
    sc = build_scenario(
        model_mix={"lm": 4, "moe": 2}, lm_eus=6, lm_edges=2, scale=0.05,
        seed=0, n_test_per_class=8, lm_seq_len=16, lm_vocab=64,
    )
    assert sc.is_hetero and len(sc.public) == 2
    asn = sc.assign("dba").lam
    res = sc.simulate(asn, cloud_rounds=1, seed=0, engine="sync")
    assert np.isfinite(res.history[0].mean_local_loss)
    assert set(res.final_params) == {"lm", "moe"}

"""ClientProgram abstraction tests: registry, per-program FlatPack
round-trips, store dtype handling, MLP engine/reference equivalence,
sequence-program (LM/MoE/Mamba/RWKV) end-to-end smokes and reference parity,
FedSGD single-step semantics + gradient uplink accounting, heterogeneous
per-client hyperparameters (cohort grouping, mixed-vs-solo bit identity,
RNG parity), and the async multicast-uplink accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hfl import HFLSchedule
from repro.data.synthetic_health import Dataset
from repro.engine import AsyncHFLEngine, BatchedSyncEngine, DeviceShardStore, FlatPack
from repro.engine.cohort import CohortPlan, LocalJob, run_cohorts
from repro.federated import build_scenario
from repro.federated.client import FLClient
from repro.federated.programs import (
    PROGRAMS,
    SEQUENCE_PROGRAMS,
    CNNProgram,
    FedSGDProgram,
    LMProgram,
    MambaProgram,
    MLPProgram,
    MoEProgram,
    RWKVProgram,
    as_program,
    tiny_lm_config,
    tiny_mamba_config,
    tiny_moe_config,
    tiny_rwkv_config,
)
from repro.models.cnn1d import HEARTBEAT_CNN, CNNConfig


def _programs():
    return [
        CNNProgram(CNNConfig(in_channels=1, n_classes=3, seq_len=32, c1=4, c2=4, hidden=8)),
        MLPProgram(feat=(32, 1), classes=3, hidden=8),
        LMProgram(
            cfg=tiny_lm_config(vocab_size=32, seq_len=8, d_model=8, n_layers=2,
                               n_heads=2, d_ff=16),
            seq_len=8,
            n_topics=3,
        ),
        MoEProgram(
            cfg=tiny_moe_config(vocab_size=32, seq_len=8, d_model=8, n_layers=2,
                                n_heads=2, d_ff=8, n_experts=4, top_k=2),
            seq_len=8,
            n_topics=3,
        ),
        MambaProgram(
            cfg=tiny_mamba_config(vocab_size=32, seq_len=8, d_model=16, n_layers=2,
                                  n_heads=2, d_ff=16, d_state=4),
            seq_len=8,
            n_topics=3,
        ),
        RWKVProgram(
            cfg=tiny_rwkv_config(vocab_size=32, seq_len=8, d_model=16, n_layers=2,
                                 d_ff=16, head_size=8),
            seq_len=8,
            n_topics=3,
        ),
        FedSGDProgram(base=MLPProgram(feat=(32, 1), classes=3, hidden=8), grad_bits=16),
    ]


# -- registry ---------------------------------------------------------------
def test_registry_has_all_programs():
    assert {"cnn", "mlp", "lm", "moe", "mamba", "rwkv", "fedsgd"} <= set(PROGRAMS.names())
    assert PROGRAMS.get("cnn")().name == "cnn"
    assert PROGRAMS.get("mlp")(feat=(10, 2), n_classes=4).n_classes == 4
    lm = PROGRAMS.get("lm")(vocab_size=64, seq_len=16, n_topics=3)
    assert lm.feat_dtype == np.int32 and lm.feat_shape == (16,)
    for name in ("moe", "mamba", "rwkv"):
        p = PROGRAMS.get(name)(vocab_size=64, seq_len=16, n_topics=3)
        assert p.name == name
        assert p.feat_dtype == np.int32 and p.feat_shape == (16,) and p.n_classes == 3
    fs = PROGRAMS.get("fedsgd")(base="mlp", feat=(10, 2), n_classes=4)
    assert fs.name == "fedsgd-mlp" and fs.single_step and fs.n_classes == 4


def test_as_program_coerces_cnn_config():
    p = as_program(HEARTBEAT_CNN)
    assert isinstance(p, CNNProgram) and p.cfg is HEARTBEAT_CNN
    assert as_program(p) is p
    with pytest.raises(TypeError):
        as_program("cnn")


def test_programs_are_hashable_jit_keys():
    """Frozen dataclasses: value-equal programs must share one jit cache key."""
    for p in _programs():
        q = type(p)(**{f.name: getattr(p, f.name) for f in p.__dataclass_fields__.values()})
        assert p == q and hash(p) == hash(q)


# -- FlatPack round-trips ---------------------------------------------------
@pytest.mark.parametrize("program", _programs(), ids=lambda p: p.name)
def test_flatpack_round_trip_exact(program):
    """ravel -> unravel must be EXACT for every program's parameter pytree
    (the engines' correctness rests on this identity)."""
    params = program.init(jax.random.PRNGKey(0))
    pack = FlatPack(params)
    flat = pack.ravel(params)
    assert flat.shape == (pack.dim,)
    back = pack.unravel(flat)
    la, lb = jax.tree.leaves(params), jax.tree.leaves(back)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("program", _programs(), ids=lambda p: p.name)
def test_flatpack_batched_round_trip_exact(program):
    """(C, D) matrix <-> cohort-stacked tree, the device pipeline's layout."""
    trees = [program.init(jax.random.PRNGKey(i)) for i in range(3)]
    pack = FlatPack(trees[0])
    mat = pack.stack(trees)
    assert mat.shape == (3, pack.dim)
    stacked = pack.unravel_batched(mat)
    back = pack.ravel_batched(stacked)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(mat))
    for c, tree in enumerate(trees):
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b[c]))


def test_flatpack_rejects_mixed_dtype_trees():
    with pytest.raises(ValueError):
        FlatPack({"a": jnp.zeros((3,), jnp.float32), "b": jnp.zeros((2,), jnp.int32)})


# -- device shard store: token shards ---------------------------------------
def test_store_gathers_int_token_shards():
    rng = np.random.default_rng(0)
    program = _programs()[2]
    clients = [
        FLClient(i, Dataset(rng.integers(0, 32, (5 + i, 8), dtype=np.int32),
                            np.full(5 + i, i % 3, np.int32), 3), program)
        for i in range(3)
    ]
    store = DeviceShardStore(clients)
    assert store.x.dtype == jnp.int32
    idx = np.stack([rng.integers(0, 5 + i, (2, 4)) for i in range(3)])
    xb, yb = store.gather(np.arange(3), idx)
    assert xb.dtype == jnp.int32 and xb.shape == (3, 2, 4, 8)
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(xb[i]), clients[i].shard.x[idx[i]])
        np.testing.assert_array_equal(np.asarray(yb[i]), clients[i].shard.y[idx[i]])


def test_cohort_plan_splits_mixed_programs():
    """Since ISSUE 5 a plan may hold a heterogeneous-model population: the
    cohort key leads with program identity, so two architectures NEVER
    stack into one (C, D) cohort — each drawn group carries its program."""
    rng = np.random.default_rng(0)
    shard = Dataset(rng.normal(size=(4, 32, 1)).astype(np.float32),
                    np.zeros(4, np.int32), 3)
    cnn, mlp = _programs()[:2]
    clients = [FLClient(0, shard, cnn), FLClient(1, shard, mlp),
               FLClient(2, shard, cnn)]
    plan = CohortPlan(clients)
    groups, passthrough = plan.draw(np.random.default_rng(1), np.ones(3, bool), 1)
    assert len(passthrough) == 0
    by_prog = {g.program.name: tuple(g.members) for g in groups}
    assert by_prog == {"cnn": (0, 2), "mlp": (1,)}


# -- MLP: full pipeline equivalence -----------------------------------------
@pytest.fixture(scope="module")
def mlp_scenario():
    return build_scenario("heartbeat", model="mlp", scale=0.02, seed=0,
                          n_test_per_class=20)


def test_mlp_scenario_wiring(mlp_scenario):
    sc = mlp_scenario
    assert sc.program.name == "mlp"
    assert sc.clients[0].program is sc.program
    assert sc.name == "heartbeat-mlp"


def test_mlp_host_vs_device_pipeline_equivalence(mlp_scenario):
    """The acceptance bar: the sync engine and the reference simulator
    agree to 1e-6 for the MLP.  The MLP has a single formulation (no conv
    reassociation), so what differs is the order of the FedAvg sums
    (segment mean vs tree mean): after one round the parameter vectors
    agree to 1e-6 elementwise, and over two rounds (Adam amplifies the
    1-ulp aggregation difference) the metrics stay pinned at 1e-6 with
    params within 2e-5.  (The name is kept from when the sync engine had a
    host pipeline to compare with.)"""
    sc = mlp_scenario
    a = sc.assign("eara-sca")
    one = {
        engine: sc.simulate(a.lam, cloud_rounds=1, seed=11, upp=1.0, engine=engine)
        for engine in ("reference", "sync")
    }
    for a_, b_ in zip(
        jax.tree.leaves(one["reference"].final_params),
        jax.tree.leaves(one["sync"].final_params),
    ):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_), atol=1e-6)
    runs = {
        engine: sc.simulate(a.lam, cloud_rounds=2, seed=11, upp=1.0, engine=engine)
        for engine in ("reference", "sync")
    }
    ref, dev = runs["reference"], runs["sync"]
    for mr, md in zip(ref.history, dev.history):
        assert md.test_acc == pytest.approx(mr.test_acc, abs=1e-6)
        assert md.mean_local_loss == pytest.approx(mr.mean_local_loss, abs=1e-6)
    assert dev.accountant.eu_traffic_bits() == ref.accountant.eu_traffic_bits()
    for a_, b_ in zip(jax.tree.leaves(ref.final_params), jax.tree.leaves(dev.final_params)):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_), atol=2e-5)


def test_mlp_host_vs_device_stress_schedule(mlp_scenario):
    """Multi-epoch schedule + partial participation: Adam amplifies the
    FedAvg reassociation round over round (same effect the CNN tests
    document), so params track to float tolerance and metrics stay pinned.
    The sync engine is held to the reference simulator; the name is kept
    from when it had a host pipeline to compare with."""
    sc = mlp_scenario
    a = sc.assign("eara-sca")
    runs = {
        engine: sc.simulate(a.lam, cloud_rounds=2, schedule=HFLSchedule(2, 2),
                            seed=11, upp=0.8, engine=engine)
        for engine in ("reference", "sync")
    }
    ref, dev = runs["reference"], runs["sync"]
    for mr, md in zip(ref.history, dev.history):
        assert md.test_acc == pytest.approx(mr.test_acc, abs=1e-6)
        assert md.mean_local_loss == pytest.approx(mr.mean_local_loss, abs=1e-5)
    assert dev.accountant.eu_traffic_bits() == ref.accountant.eu_traffic_bits()
    for a_, b_ in zip(jax.tree.leaves(ref.final_params), jax.tree.leaves(dev.final_params)):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_), atol=1e-3)


def test_mlp_sync_engine_matches_reference(mlp_scenario):
    """Same RNG-stream parity guarantee as the CNN: the batched engine must
    reproduce the reference simulator for any program."""
    sc = mlp_scenario
    a = sc.assign("eara-sca")
    ref = sc.simulate(a.lam, cloud_rounds=2, seed=0, upp=1.0)
    eng = sc.simulate(a.lam, cloud_rounds=2, seed=0, upp=1.0, engine="sync",
                      backend="reference")
    for mr, me in zip(ref.history, eng.history):
        assert me.test_acc == pytest.approx(mr.test_acc, abs=1e-6)
        assert me.mean_local_loss == pytest.approx(mr.mean_local_loss, abs=1e-5)


# -- LM: end-to-end smoke ----------------------------------------------------
@pytest.fixture(scope="module")
def lm_scenario():
    return build_scenario("lm", scale=0.05, seed=0, n_test_per_class=8,
                          lm_eus=6, lm_edges=2, lm_topics=3, lm_seq_len=16,
                          lm_vocab=64)


def test_lm_scenario_topic_imbalance(lm_scenario):
    """Topic skew must give the KLD-aware assignment something to exploit."""
    sc = lm_scenario
    assert sc.program.name == "lm"
    assert sc.class_counts.shape == (6, 3)
    for i, c in enumerate(sc.clients):
        assert c.shard.x.dtype == np.int32
        np.testing.assert_array_equal(c.class_counts(), sc.class_counts[i])
    # every EU is topic-dominated (the non-IID skew EARA exploits) ...
    frac = sc.class_counts.max(axis=1) / sc.class_counts.sum(axis=1)
    assert (frac > 0.5).all()
    # ... and KLD-aware assignment beats distance-based, as in the paper
    assert sc.assign("eara-sca").kld_total <= sc.assign("dba").kld_total + 1e-6
    assert sc.assign("eara-dca").kld_total <= sc.assign("eara-sca").kld_total + 1e-6


def test_lm_trains_through_batched_sync_engine(lm_scenario):
    """2-round LM smoke through the device pipeline: history populated, loss
    finite and non-degenerate, accountant consistent with the LM's size."""
    sc = lm_scenario
    a = sc.assign("eara-sca")
    res = sc.simulate(a.lam, cloud_rounds=2, seed=0, engine="sync")
    assert len(res.history) == 2
    for m in res.history:
        assert 0.0 <= m.test_acc <= 1.0
        assert np.isfinite(m.mean_local_loss) and m.mean_local_loss > 0.0
    # 2 cloud rounds of the tiny transformer: traffic = 2 * (up + down) * M
    assert res.accountant.cloud_rounds == 2
    assert sum(res.accountant.eu_traffic_bits().values()) == pytest.approx(
        2 * 2 * sc.model_bits * len(sc.clients)
    )


# -- async accounting: multicast per dispatch --------------------------------
def _tiny_population(dual: bool):
    rng = np.random.default_rng(0)
    program = MLPProgram(feat=(8, 1), classes=2, hidden=4)
    clients = [
        FLClient(i, Dataset(rng.normal(size=(4, 8, 1)).astype(np.float32),
                            rng.integers(0, 2, 4).astype(np.int32), 2), program)
        for i in range(4)
    ]
    test = Dataset(rng.normal(size=(8, 8, 1)).astype(np.float32),
                   rng.integers(0, 2, 8).astype(np.int32), 2)
    asn = np.zeros((4, 2))
    asn[np.arange(4), np.arange(4) % 2] = 1.0
    if dual:
        asn[0, :] = 1.0  # EU0 dual-homed
    return program, clients, test, asn


# -- MoE / Mamba / RWKV: end-to-end on every engine ---------------------------
@pytest.fixture(scope="module", params=("moe", "mamba", "rwkv"))
def seq_model_runs(request):
    """One tiny topic-skewed scenario per sequence model, simulated 2 cloud
    rounds on the sync engine, the reference simulator, and async.
    Module-scoped per model so the (compile-heavy) runs happen once and
    every assertion reuses them."""
    model = request.param
    sc = build_scenario(model=model, scale=0.04, seed=0, n_test_per_class=6,
                        lm_eus=5, lm_edges=2, lm_topics=3, lm_seq_len=16,
                        lm_vocab=64)
    a = sc.assign("eara-sca")
    runs = {
        "device": sc.simulate(a.lam, cloud_rounds=2, seed=3, engine="sync"),
        "reference": sc.simulate(a.lam, cloud_rounds=2, seed=3, engine="reference"),
        "async": sc.simulate(a.lam, cloud_rounds=2, seed=3, engine="async"),
    }
    return model, sc, runs


def test_seq_program_scenario_wiring(seq_model_runs):
    model, sc, _ = seq_model_runs
    assert sc.program.name == model and sc.name == model
    assert sc.program.feat_dtype == np.int32
    assert sc.class_counts.shape == (5, 3)
    # topic skew present: the imbalance EARA needs
    frac = sc.class_counts.max(axis=1) / sc.class_counts.sum(axis=1)
    assert (frac > 0.5).all()


@pytest.mark.parametrize("engine", ["device", "reference", "async"])
def test_seq_program_trains_two_rounds(seq_model_runs, engine):
    """Acceptance bar: >= 2 cloud rounds on the sync engine, the reference
    simulator AND the async engine with finite, non-degenerate loss for
    every new program."""
    model, sc, runs = seq_model_runs
    res = runs[engine]
    assert len(res.history) == 2
    for m in res.history:
        assert 0.0 <= m.test_acc <= 1.0
        assert np.isfinite(m.mean_local_loss) and m.mean_local_loss > 0.0
    assert res.accountant.cloud_rounds == 2


def test_seq_program_host_vs_device_parity(seq_model_runs):
    """The sequence programs have a single formulation, so the sync engine
    and the reference simulator differ only in summation order — metrics
    must agree to float tolerance (same bar as the MLP parity tests).  The
    name is kept from when the sync engine had a host pipeline."""
    _, _, runs = seq_model_runs
    ref, dev = runs["reference"], runs["device"]
    for mr, md in zip(ref.history, dev.history):
        assert md.test_acc == pytest.approx(mr.test_acc, abs=1e-6)
        assert md.mean_local_loss == pytest.approx(mr.mean_local_loss, abs=1e-5)
    assert dev.accountant.eu_traffic_bits() == ref.accountant.eu_traffic_bits()


# -- FedSGD: single-step semantics + gradient uplink accounting ---------------
def _fedsgd_population(grad_bits: int):
    rng = np.random.default_rng(1)
    program = FedSGDProgram(base=MLPProgram(feat=(8, 1), classes=2, hidden=4),
                            grad_bits=grad_bits)
    clients = [
        FLClient(i, Dataset(rng.normal(size=(6, 8, 1)).astype(np.float32),
                            rng.integers(0, 2, 6).astype(np.int32), 2), program)
        for i in range(4)
    ]
    test = Dataset(rng.normal(size=(8, 8, 1)).astype(np.float32),
                   rng.integers(0, 2, 8).astype(np.int32), 2)
    asn = np.zeros((4, 2))
    asn[np.arange(4), np.arange(4) % 2] = 1.0
    return program, clients, test, asn


def test_fedsgd_takes_one_sgd_step():
    """The wrapper's whole contract: whatever the schedule or the client's
    local_epochs say, local work is ONE plain-SGD step — the uploaded
    delta is exactly -lr * grad on the drawn batch."""
    program, clients, test, asn = _fedsgd_population(grad_bits=32)
    clients[0].local_epochs = 3  # must be overridden by single_step
    assert all(c.plan_steps() == 1 for c in clients)
    assert clients[0].epochs_for(5) == 1
    start = program.init(jax.random.PRNGKey(7))
    rng = np.random.default_rng(0)
    rng2 = np.random.default_rng(0)
    upd, _ = clients[0].local_update(start, rng, epochs=5)
    # replicate the single draw and take the step by hand
    n = len(clients[0].shard)
    idx = rng2.permutation(n)
    need = clients[0].batch_size
    if need > n:
        idx = np.concatenate([idx, rng2.integers(0, n, need - n)])
    idx = idx[:need]
    x = jnp.asarray(clients[0].shard.x[idx])
    y = jnp.asarray(clients[0].shard.y[idx])
    grads = jax.grad(lambda p: program.loss(p, x, y))(start)
    for leaf_u, leaf_s, leaf_g in zip(
        jax.tree.leaves(upd), jax.tree.leaves(start), jax.tree.leaves(grads)
    ):
        np.testing.assert_allclose(
            np.asarray(leaf_u), np.asarray(leaf_s) - clients[0].lr * np.asarray(leaf_g),
            atol=1e-7,
        )


@pytest.mark.parametrize("grad_bits", [32, 16])
def test_fedsgd_gradient_uplink_accounting(grad_bits):
    """Distinct uplink accounting: the EU->edge payload is a gradient at
    grad_bits per parameter (downlink stays a full model broadcast), and
    the engines agree with the reference simulator on both the bits and
    the trajectory."""
    from repro.federated.simulation import HFLSimulation

    program, clients, test, asn = _fedsgd_population(grad_bits)
    ref = HFLSimulation(clients, asn, program, test, seed=0)
    r_ref = ref.run(2)
    eng = BatchedSyncEngine(clients, asn, program, test, seed=0)
    r_eng = eng.run(2)
    bits = eng.accountant.model_bits
    for i in range(len(clients)):
        assert eng.accountant.eu_bits_up[i] == pytest.approx(
            2 * bits * grad_bits / 32.0
        )
        assert eng.accountant.eu_bits_down[i] == pytest.approx(2 * bits)
    assert ref.accountant.eu_bits_up == pytest.approx(eng.accountant.eu_bits_up)
    for mr, me in zip(r_ref.history, r_eng.history):
        assert me.test_acc == pytest.approx(mr.test_acc, abs=1e-6)
        assert me.mean_local_loss == pytest.approx(mr.mean_local_loss, abs=1e-5)


def test_fedsgd_fp16_quantization_is_applied():
    """grad_bits=16 must CHANGE the uploaded update (fp16 cast applied, not
    just accounted) while grad_bits=32 is an exact passthrough."""
    program16 = FedSGDProgram(base=MLPProgram(feat=(4, 1), classes=2, hidden=2),
                              grad_bits=16)
    start = jnp.zeros((5,), jnp.float32)
    trained = jnp.asarray([1.0, 1e-9, -2.5, 3.0e-8, 0.1], jnp.float32)
    q = program16.quantize_upload(start, trained)
    np.testing.assert_array_equal(
        np.asarray(q), np.asarray(trained.astype(jnp.float16).astype(jnp.float32))
    )
    program32 = FedSGDProgram(base=program16.base, grad_bits=32)
    assert program32.quantize_upload(start, trained) is trained
    with pytest.raises(ValueError):
        FedSGDProgram(base=program16.base, grad_bits=8)
    with pytest.raises(TypeError):
        FedSGDProgram(base=program16)


# -- heterogeneous per-client hyperparameters --------------------------------
def _hetero_clients(program, rng, sizes, hparams):
    clients = []
    for i, (n, hp) in enumerate(zip(sizes, hparams)):
        shard = Dataset(rng.normal(size=(n, 16, 1)).astype(np.float32),
                        rng.integers(0, 3, n).astype(np.int32), 3)
        clients.append(FLClient(i, shard, program, **hp))
    return clients


def test_cohort_plan_groups_by_hparam_tuple():
    """Clients split into one fixed-shape cohort per distinct
    (steps, batch, lr, epochs) tuple; draws stay in global client order."""
    rng = np.random.default_rng(0)
    program = MLPProgram(feat=(16, 1), classes=3, hidden=4)
    hps = [dict(lr=1e-3), dict(lr=1e-3), dict(lr=5e-3, local_epochs=2),
           dict(lr=5e-3, local_epochs=2), dict(lr=1e-3)]
    clients = _hetero_clients(program, rng, [8] * 5, hps)
    plan = CohortPlan(clients)
    groups, passthrough = plan.draw(np.random.default_rng(1), np.ones(5, bool), 1)
    assert len(passthrough) == 0
    by_members = {tuple(g.members): g for g in groups}
    assert set(by_members) == {(0, 1, 4), (2, 3)}
    g_a, g_b = by_members[(0, 1, 4)], by_members[(2, 3)]
    assert g_a.lr == 1e-3 and g_a.epochs == 1 and g_a.idx.shape == (3, 1, 1, 10)
    assert g_b.lr == 5e-3 and g_b.epochs == 2 and g_b.idx.shape == (2, 2, 1, 10)


def test_mixed_hparam_cohorts_bit_identical_to_solo():
    """Acceptance bar: a mixed-hyperparameter cohort batch produces
    BIT-identical trained rows to running each hyperparameter group alone
    (same starts, same drawn indices) — grouping isolates the groups'
    computations exactly."""
    rng = np.random.default_rng(0)
    program = MLPProgram(feat=(16, 1), classes=3, hidden=4)
    hps = [dict(lr=1e-3)] * 2 + [dict(lr=5e-3, local_epochs=2)] * 2
    clients = _hetero_clients(program, rng, [8] * 4, hps)
    pack = FlatPack(program.init(jax.random.PRNGKey(0)))
    start = pack.ravel(program.init(jax.random.PRNGKey(1)))

    def jobs_for(cs):
        # fixed per-client index draws so mixed and solo see identical data
        out = []
        for c in cs:
            epochs = c.epochs_for(1)
            idx = [np.random.default_rng(100 + c.cid).integers(0, 8, (1, 10))
                   for _ in range(epochs)]
            out.append(LocalJob(c, start, idx, steps=1))
        return out

    mixed = run_cohorts(jobs_for(clients), program, pack)
    solo_a = run_cohorts(jobs_for(clients[:2]), program, pack)
    solo_b = run_cohorts(jobs_for(clients[2:]), program, pack)
    for c in clients[:2]:
        np.testing.assert_array_equal(
            np.asarray(mixed.row(c.cid)), np.asarray(solo_a.row(c.cid))
        )
    for c in clients[2:]:
        np.testing.assert_array_equal(
            np.asarray(mixed.row(c.cid)), np.asarray(solo_b.row(c.cid))
        )
    assert mixed.loss == {**solo_a.loss, **solo_b.loss}


def test_hetero_explicit_defaults_match_homogeneous_rng_parity():
    """RNG-parity pin: setting local_epochs explicitly to the schedule's
    value must leave the device-pipeline trajectory BIT-identical to the
    homogeneous run (the grouping key changes, the RNG stream must not)."""
    sc_kw = dict(scale=0.02, seed=0, n_test_per_class=10)
    base = build_scenario("heartbeat", model="mlp", **sc_kw)
    hp = [dict(local_epochs=2)] * len(base.clients)
    explicit = build_scenario("heartbeat", model="mlp", hparams=hp, **sc_kw)
    a = base.assign("eara-sca")
    kw = dict(cloud_rounds=2, schedule=HFLSchedule(2, 1), seed=5, engine="sync")
    r_base = base.simulate(a.lam, **kw)
    r_expl = explicit.simulate(a.lam, **kw)
    for la, lb in zip(jax.tree.leaves(r_base.final_params),
                      jax.tree.leaves(r_expl.final_params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_hetero_hparams_engine_matches_reference():
    """Two distinct (lr, local-epochs) groups: the batched engine must
    reproduce the reference simulator's trajectory (the reference trains
    each client sequentially with its own hyperparameters, so this parity
    IS the per-group-correctness guarantee end to end)."""
    m = 18
    hp = [dict(lr=1e-3, local_epochs=1)] * (m // 2) + \
         [dict(lr=5e-4, local_epochs=2)] * (m - m // 2)
    sc = build_scenario("heartbeat", model="mlp", hparams=hp, scale=0.02,
                        seed=0, n_test_per_class=10)
    assert {(c.lr, c.local_epochs) for c in sc.clients} == {(1e-3, 1), (5e-4, 2)}
    a = sc.assign("eara-sca")
    ref = sc.simulate(a.lam, cloud_rounds=2, seed=0)
    res = sc.simulate(a.lam, cloud_rounds=2, seed=0, engine="sync")
    for mr, me in zip(ref.history, res.history):
        assert me.test_acc == pytest.approx(mr.test_acc, abs=1e-6)
        assert me.mean_local_loss == pytest.approx(mr.mean_local_loss, abs=1e-5)
    assert res.accountant.eu_traffic_bits() == ref.accountant.eu_traffic_bits()


# -- async accounting: multicast per dispatch --------------------------------
@pytest.mark.parametrize("dual", [False, True])
def test_async_uplink_matches_sync_multicast_accounting(dual):
    """One multicast uplink per client per dispatch: under dual-connectivity
    the async accountant must charge EU0 payload*(1+3%) per round — exactly
    the sync semantics — instead of a full uplink per (client, edge)
    membership (the divergence documented since PR 1, closed here)."""
    program, clients, test, asn = _tiny_population(dual)
    sync = BatchedSyncEngine(clients, asn, program, test, seed=0)
    sync.run(1)
    lat = np.full(asn.shape, 0.01)
    eng = AsyncHFLEngine(clients, asn, program, test, latency=lat, seed=0,
                         quorum=1.0, staleness_decay=1.0)
    eng.run(1)
    assert eng.accountant.eu_bits_up == pytest.approx(sync.accountant.eu_bits_up)
    assert eng.accountant.eu_bits_down == pytest.approx(sync.accountant.eu_bits_down)
    if dual:
        bits = eng.accountant.model_bits
        assert eng.accountant.eu_bits_up[0] == pytest.approx(1.03 * bits)
        assert eng.accountant.eu_bits_down[0] == pytest.approx(2.0 * bits)

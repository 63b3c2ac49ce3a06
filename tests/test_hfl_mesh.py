"""Hierarchical-FL-on-mesh semantics.

Part 1: CPU functional tests of the ``hfl_mesh`` train-step (no mesh).
Part 2: the ``MeshSyncEngine`` cross-mesh parity + comm-accounting harness —
every mesh size available to the process (1 locally; {1, 2, 4, 8} in the CI
multi-device job, which runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) must reproduce the
single-device ``BatchedSyncEngine`` trajectory <= 1e-6 and the golden pins,
with the cloud reduce as the only cross-edge collective in compiled HLO.  A
subprocess test covers the multi-device sizes even when the main process
sees one device.
"""
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.hfl import HFLSchedule
from repro.distributed.axes import edge_mesh, grad_cast, sharding_hints
from repro.distributed.hfl_mesh import (
    init_hfl_state,
    make_hfl_train_step,
    replicate_for_edges,
)
from repro.engine import BatchedSyncEngine
from repro.engine.mesh_sim import MeshSyncEngine, mesh_segment_mean
from repro.models import init_params
from repro.training.optimizers import adam

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("phi3-mini-3.8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = adam(1e-3)
    E, B, S = 2, 4, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (E, B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 2)}
    return cfg, params, opt, batch


def test_replicas_diverge_then_sync(setup):
    cfg, params, opt, batch = setup
    state = init_hfl_state(params, opt, 2)
    local = jax.jit(make_hfl_train_step(cfg, opt, sync=False))
    syncs = jax.jit(make_hfl_train_step(cfg, opt, sync=True))
    state, _ = local(state, batch)
    div = max(jax.tree.leaves(jax.tree.map(
        lambda x: float(jnp.max(jnp.abs(x[0] - x[1]))), state.params)))
    assert div > 0  # non-IID per-edge batches -> replicas diverge
    state, _ = syncs(state, batch)
    div2 = max(jax.tree.leaves(jax.tree.map(
        lambda x: float(jnp.max(jnp.abs(x[0] - x[1]))), state.params)))
    assert div2 < 1e-6  # cloud sync equalizes replicas (eq. 8)


def test_sigma_weighted_cloud_average(setup):
    cfg, params, opt, batch = setup
    w = jnp.asarray([3.0, 1.0])
    state = init_hfl_state(params, opt, 2)
    # hand-divergent replicas
    state = state._replace(params=jax.tree.map(
        lambda x: x.at[1].set(x[1] + 1.0), state.params))
    syncs = jax.jit(make_hfl_train_step(cfg, opt, sync=True, edge_weights=w))
    new, _ = syncs(state, batch)
    # after sync every replica equals the sigma-weighted average
    for leaf in jax.tree.leaves(new.params):
        np.testing.assert_allclose(np.asarray(leaf[0]), np.asarray(leaf[1]), atol=1e-5)


def test_grad_cast_identity_forward_and_matching_backward():
    """grad_cast is identity in forward; the cotangent is pinned to the
    primal dtype at the gate (so later resharding moves bf16)."""
    x = jnp.ones((4,), jnp.bfloat16)

    def f(x):
        y = grad_cast(x * jnp.bfloat16(2.0))
        return jnp.sum(y.astype(jnp.float32) * 3.0)

    assert float(f(x)) == 24.0
    g = jax.grad(f)(x)
    assert g.dtype == jnp.bfloat16
    assert float(g[0]) == 6.0


def test_sharding_hints_scoped():
    from repro.distributed.axes import current_hints

    assert current_hints().batch_axes is None

    class M:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 2}

    with sharding_hints(M()):
        assert current_hints().batch_axes == ("data",)
        assert current_hints().model_size == 2
    assert current_hints().batch_axes is None


def test_bf16_moment_adam_converges():
    opt = adam(0.1, moment_dtype=jnp.bfloat16)
    params = {"x": jnp.asarray(5.0)}
    state = opt.init(params)
    assert jax.tree.leaves(state)[0].dtype == jnp.bfloat16
    for i in range(120):
        params, state = opt.update(params, {"x": 2 * params["x"]}, state, jnp.asarray(i))
    assert abs(float(params["x"])) < 0.05


# -- MeshSyncEngine: cross-mesh parity + comm accounting ---------------------
_M, _E = 24, 8
_SCHED = HFLSchedule(2, 2)  # T = 2 edge rounds per cloud round
_ROUNDS = 2
_KS = (1, 2, 4, 8)
_GOLDEN_MESH = os.path.join(
    os.path.dirname(__file__), "golden", "mesh_trajectory.json"
)


def _flat_params(tree) -> np.ndarray:
    return np.concatenate(
        [np.ravel(np.asarray(l)) for l in jax.tree_util.tree_leaves(tree)]
    )


def _params_hash(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def mesh_pop():
    from tools.golden_mesh import make_population

    return make_population(_M, _E)


@pytest.fixture(scope="module")
def base_run(mesh_pop):
    clients, asn, test, program = mesh_pop
    eng = BatchedSyncEngine(
        clients, asn, program, test, schedule=_SCHED, seed=0
    )
    return eng.run(_ROUNDS, eval_every=1)


@pytest.fixture(scope="module")
def mesh_runs(mesh_pop):
    """(SimResult, comm_report) per mesh size the process can build."""
    clients, asn, test, program = mesh_pop
    out = {}
    for k in _KS:
        if k > jax.device_count():
            continue
        eng = MeshSyncEngine(
            clients, asn, program, test, schedule=_SCHED, seed=0, mesh=k
        )
        out[k] = (eng.run(_ROUNDS, eval_every=1), eng.comm_report())
    return out


@pytest.mark.parametrize("k", _KS)
def test_mesh_matches_batched_sync(mesh_runs, base_run, k):
    """Every mesh size reproduces the single-device engine trajectory:
    accuracies exactly, parameters <= 1e-6 (the cloud psum's association
    differs from ``flat_mean`` at k > 1; everything edge-local is
    bit-identical by construction)."""
    if k not in mesh_runs:
        pytest.skip(f"needs {k} devices, process sees {jax.device_count()}")
    res, _rep = mesh_runs[k]
    assert [m.test_acc for m in res.history] == [
        m.test_acc for m in base_run.history
    ]
    diff = np.max(np.abs(_flat_params(res.final_params) - _flat_params(base_run.final_params)))
    assert diff <= 1e-6, f"k={k}: max |dparam| {diff}"
    if k == 1:
        assert diff == 0.0  # single device: bit-identical, not just close


def test_mesh_matches_reference(mesh_pop, mesh_runs):
    """The mesh path also tracks the readable reference simulator (same RNG
    stream discipline as the batched engine it subclasses)."""
    from repro.federated import HFLSimulation

    clients, asn, test, program = mesh_pop
    sim = HFLSimulation(
        clients, asn, program, test, schedule=_SCHED, seed=0
    )
    ref = sim.run(_ROUNDS, eval_every=1)
    res, _ = mesh_runs[1]
    np.testing.assert_allclose(
        [m.test_acc for m in res.history],
        [m.test_acc for m in ref.history],
        atol=1e-6,
    )
    np.testing.assert_allclose(
        _flat_params(res.final_params), _flat_params(ref.final_params), atol=1e-5
    )


def test_mesh_comm_ledger_structure(mesh_runs):
    """The HLO ledger pins the paper's communication structure: the edge
    round's programs (starts gather, cohort epoch, edge FedAvg) compile to
    ZERO collective bytes, and the cloud reduce is the only program with
    collectives — cross-edge iff the mesh actually splits the edges."""
    for k, (_res, rep) in mesh_runs.items():
        progs = rep["programs"]
        assert {"edge_starts", "cohort_epoch", "edge_agg", "cloud_reduce"} <= set(progs)
        for name in ("edge_starts", "cohort_epoch", "edge_agg"):
            assert progs[name]["coll_bytes_per_call"] == 0.0, (k, name)
            assert progs[name]["cross_edge_bytes_total"] == 0.0, (k, name)
        assert progs["cloud_reduce"]["calls"] == _ROUNDS
        assert rep["edge_rounds"] == _ROUNDS * _SCHED.edge_per_cloud
        if k == 1:
            assert rep["cross_edge_total_bytes"] == 0.0
        else:
            # one model payload per cloud sync, amortized 1/T per edge round
            payload = rep["payload_bytes"]
            assert rep["cross_edge_bytes_per_cloud_round"] == pytest.approx(
                payload, rel=0.05
            )
            assert rep["cross_edge_bytes_per_edge_round"] == pytest.approx(
                payload / _SCHED.edge_per_cloud, rel=0.05
            )


@pytest.fixture(scope="module")
def golden_mesh():
    with open(_GOLDEN_MESH) as f:
        data = json.load(f)
    if data["jax"] != jax.__version__ or data["backend"] != jax.default_backend():
        pytest.skip(
            f"mesh pins recorded on jax {data['jax']}/{data['backend']}, "
            f"running {jax.__version__}/{jax.default_backend()} — regenerate "
            "with tools/golden_mesh.py"
        )
    return data


@pytest.mark.parametrize("k", _KS)
def test_mesh_golden_trajectory_pinned(golden_mesh, mesh_runs, k):
    """Per-mesh-size golden pins (tools/golden_mesh.py): the accuracy
    history and the final-parameter bytes must reproduce exactly, so mesh
    refactors cannot silently drift any device count's trajectory."""
    if k not in mesh_runs:
        pytest.skip(f"needs {k} devices, process sees {jax.device_count()}")
    res, _ = mesh_runs[k]
    want = golden_mesh["runs"][f"k{k}"]
    assert [round(m.test_acc, 10) for m in res.history] == want["accs"]
    assert _params_hash(res.final_params) == want["params_sha256"]


def test_mesh_rejects_unsupported(mesh_pop):
    clients, asn, test, program = mesh_pop
    kw = dict(schedule=_SCHED, seed=0)
    dca = asn.copy()
    dca[0, (asn[0].argmax() + 1) % _E] = 1.0  # client 0 on two edges
    with pytest.raises(ValueError, match="single-connectivity"):
        MeshSyncEngine(clients, dca, program, test, **kw)
    with pytest.raises(ValueError):
        MeshSyncEngine(clients, asn, program, test, mesh=3, **kw)  # 8 % 3
    from repro.faults import FaultSpec

    with pytest.raises(ValueError, match="fault"):
        MeshSyncEngine(
            clients, asn, program, test, faults=FaultSpec(seed=0), **kw
        )


def test_edge_mesh_axis_and_bounds():
    m = edge_mesh(1)
    assert m.axis_names == ("edge",)
    assert m.shape["edge"] == 1
    with pytest.raises(ValueError):
        edge_mesh(jax.device_count() + 1)
    with pytest.raises(ValueError):
        edge_mesh(0)


def test_scenario_mesh_pipeline_wires_comm_report():
    from repro.federated import build_scenario

    sc = build_scenario("heartbeat", scale=0.02, seed=0, n_test_per_class=10)
    asn = sc.assign("eara-sca").lam
    res = sc.simulate(asn, 1, engine="sync", mesh=1, seed=0)
    assert res.comm_report["devices"] >= 1
    assert "cloud_reduce" in res.comm_report["programs"]
    assert np.isfinite(res.history[-1].test_acc)


# -- satellite: sharded edge FedAvg == flat_segment_mean == numpy ------------
def test_mesh_segment_mean_matches_references():
    """Hypothesis sweep over ragged membership maps: the mesh engine's
    sharded per-edge FedAvg equals ``flat_segment_mean`` and a numpy
    per-segment reference, for every mesh size the process offers."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.engine.flatten import flat_segment_mean

    ks = [k for k in _KS if k <= jax.device_count() and _E % k == 0]
    meshes = [edge_mesh(k) for k in ks]

    @settings(max_examples=20, deadline=None)
    @given(
        st.integers(0, 24),  # rows (clients); 0 = every edge empty
        st.integers(0, 2**31 - 1),
    )
    def prop(rows, seed):
        rng = np.random.default_rng(seed)
        d = 5
        # grid-valued data keeps every summation order exact in f32, so the
        # three formulations must agree to float-roundoff, not looser
        upd = rng.integers(-16, 17, (rows, d)).astype(np.float32) / 4.0
        seg = rng.integers(0, _E, rows)
        w = rng.integers(0, 9, rows).astype(np.float32) / 2.0
        want = np.zeros((_E, d), np.float32)
        for s in range(_E):
            sel = seg == s
            if sel.any() and w[sel].sum() > 0:
                want[s] = (upd[sel] * w[sel, None]).sum(0) / w[sel].sum()
        got_flat = np.asarray(
            flat_segment_mean(jnp.asarray(upd), jnp.asarray(seg), jnp.asarray(w), _E)
        )
        np.testing.assert_allclose(got_flat, want, atol=1e-5, rtol=1e-5)
        for mesh in meshes:
            got_mesh = mesh_segment_mean(mesh, upd, seg, w, _E)
            np.testing.assert_allclose(got_mesh, want, atol=1e-5, rtol=1e-5)

    prop()


@pytest.mark.slow
def test_mesh_parity_multidevice_subprocess(golden_mesh):
    """Subprocess with 8 virtual devices: mesh sizes {2, 4, 8} reproduce the
    single-device engine <= 1e-6 AND the golden pins, and the cloud reduce
    is the only cross-edge collective (~1 payload per cloud round) — the
    full harness even when the main process sees one device."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax
from tools.golden_mesh import make_population
from repro.core.hfl import HFLSchedule
from repro.engine import BatchedSyncEngine
from repro.engine.mesh_sim import MeshSyncEngine
import hashlib

def params_hash(tree):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()

clients, asn, test, program = make_population(%(m)d, %(e)d)
sched = HFLSchedule(2, 2)
flat = lambda p: np.concatenate([np.ravel(np.asarray(l)) for l in jax.tree_util.tree_leaves(p)])
rb = BatchedSyncEngine(clients, asn, program, test, schedule=sched,
                       seed=0).run(%(rounds)d, eval_every=1)
out = {}
for k in (2, 4, 8):
    eng = MeshSyncEngine(clients, asn, program, test, schedule=sched, seed=0, mesh=k)
    rm = eng.run(%(rounds)d, eval_every=1)
    rep = eng.comm_report()
    out[str(k)] = {
        "param_diff": float(np.max(np.abs(flat(rb.final_params) - flat(rm.final_params)))),
        "accs_equal": [m.test_acc for m in rm.history] == [m.test_acc for m in rb.history],
        "accs": [round(m.test_acc, 10) for m in rm.history],
        "hash": params_hash(rm.final_params),
        "xe_per_cloud": rep["cross_edge_bytes_per_cloud_round"],
        "payload": rep["payload_bytes"],
        "edge_xe": sum(v["cross_edge_bytes_total"] for n, v in rep["programs"].items()
                       if n != "cloud_reduce"),
    }
print(json.dumps(out))
""" % {"m": _M, "e": _E, "rounds": _ROUNDS}
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join((SRC, root)))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for k, row in res.items():
        assert row["param_diff"] <= 1e-6, (k, row["param_diff"])
        assert row["accs_equal"], k
        assert row["edge_xe"] == 0.0, k
        assert row["xe_per_cloud"] == pytest.approx(row["payload"], rel=0.05), k
        want = golden_mesh["runs"][f"k{k}"]
        assert row["accs"] == want["accs"], k
        assert row["hash"] == want["params_sha256"], k

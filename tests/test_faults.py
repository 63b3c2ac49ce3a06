"""Fault-injection layer tests (`repro.faults`):

* FaultSpec validation and FaultState unit behaviour — availability purity,
  block re-fade + drift, energy budgets, async upload-cascade planning
* cross-engine parity under faults: one FaultSpec yields the identical
  dropout schedule, accountant totals, and accuracy history on the
  reference and sync-engine paths
* per-engine same-seed determinism (params hash + totals), including the
  async retry/timeout/abandon machinery
* degraded modes: total upload loss, energy exhaustion, drift-triggered
  assignment re-repair — every engine must still complete
* the `faults=False` override and the scenario-level type/engine guards

`faults=None` bit-identity to the fault-free engines is enforced separately
by the golden-trajectory pins in test_consistency.py.
"""
import hashlib

import jax
import numpy as np
import pytest

from repro.core.assignment import repair_assignment
from repro.core.hfl import HFLSchedule
from repro.faults import FaultSpec, FaultState
from repro.federated import build_scenario

# the ISSUE's acceptance scenario: >= 20% churn, lossy uplinks with retries,
# finite batteries, per-round re-fade with slow drift
CHAOS = dict(
    p_drop=0.25, p_rejoin=0.5, p_fail=0.2, max_retries=2, backoff_s=0.1,
    energy_uploads=6.0, refade_rounds=1, drift_rate=0.05,
)


@pytest.fixture(scope="module")
def scenario():
    return build_scenario("heartbeat", model="mlp", scale=0.02, seed=0,
                          n_test_per_class=10)


@pytest.fixture(scope="module")
def lam(scenario):
    return scenario.assign("eara-sca").lam


def _state(scenario, spec):
    return FaultState(spec, scenario.topo, scenario.wp, scenario.model_bits,
                      class_counts=scenario.class_counts)


def _params_hash(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


# -- FaultSpec validation ------------------------------------------------------


@pytest.mark.parametrize("kw", [
    dict(p_drop=1.5),
    dict(p_rejoin=-0.1),
    dict(start_up=2.0),
    dict(p_fail=-1e-9),
    dict(max_retries=-1),
    dict(backoff_s=-0.5),
    dict(timeout_s=0.0),
    dict(energy_uploads=0.0),
    dict(energy_spread=1.0),
    dict(refade_rounds=-1),
    dict(drift_rate=-0.1),
])
def test_spec_validation_rejects(kw):
    with pytest.raises(ValueError):
        FaultSpec(seed=0, **kw)


def test_reassign_requires_class_counts(scenario):
    spec = FaultSpec(seed=0, reassign=True)
    with pytest.raises(ValueError, match="class_counts"):
        FaultState(spec, scenario.topo, scenario.wp, scenario.model_bits)


# -- availability churn --------------------------------------------------------


def test_availability_is_pure_in_the_spec(scenario):
    spec = FaultSpec(seed=11, p_drop=0.3, p_rejoin=0.4)
    a, b = _state(scenario, spec), _state(scenario, spec)
    # query orders differ; the Markov trace must not
    fwd = [a.availability(t) for t in (1, 2, 3, 4, 5)]
    assert np.array_equal(b.availability(5), fwd[4])
    assert np.array_equal(b.availability(2), fwd[1])
    # returned arrays are copies: callers cannot corrupt the cache
    fwd[0][:] = False
    assert a.availability(1).any() or not _state(scenario, spec).availability(1).any()


def test_availability_actually_churns(scenario):
    st = _state(scenario, FaultSpec(seed=1, p_drop=0.25, p_rejoin=0.5))
    traces = np.stack([st.availability(t) for t in range(1, 9)])
    assert traces.all(axis=1).sum() < len(traces)  # some round lost someone
    assert traces.any(axis=1).all()  # never a fully-dead population
    # rejoin happens: at least one EU goes down then comes back
    down_up = ((~traces[:-1]) & traces[1:]).any()
    assert down_up


def test_start_up_zero_begins_dark(scenario):
    st = _state(scenario, FaultSpec(seed=0, start_up=0.0, p_rejoin=1.0))
    assert not st.availability(0).any()
    assert st.availability(1).all()  # p_rejoin=1 brings everyone back


# -- time-varying channel ------------------------------------------------------


def test_refade_blocks_and_drift(scenario):
    st = _state(scenario, FaultSpec(seed=2, refade_rounds=2, drift_rate=0.0))
    f1, f2, f3 = st.fading(1), st.fading(2), st.fading(3)
    assert np.array_equal(f1, f2)  # same block
    assert not np.array_equal(f2, f3)  # new Rayleigh block
    # static mode keeps the topology's committed fade
    st0 = _state(scenario, FaultSpec(seed=2, refade_rounds=0, drift_rate=0.0))
    assert np.array_equal(st0.fading(1), np.asarray(scenario.topo.fading_mag2))
    # drift perturbs within a block
    std = _state(scenario, FaultSpec(seed=2, refade_rounds=2, drift_rate=0.05))
    assert not np.array_equal(std.fading(1), std.fading(2))
    assert np.isfinite(std.fading(5)).all() and (std.fading(5) > 0).all()


def test_cost_matrices_follow_the_fade(scenario):
    st = _state(scenario, FaultSpec(seed=3, refade_rounds=1, drift_rate=0.1))
    l1, l2 = np.asarray(st.latency(1)), np.asarray(st.latency(2))
    assert l1.shape == np.asarray(scenario.topo.dist).shape
    assert not np.array_equal(l1, l2)
    assert np.asarray(st.feasible(1)).any(axis=1).all()  # fallback holds


# -- energy budgets ------------------------------------------------------------


def test_energy_budget_debit_and_death(scenario):
    spec = FaultSpec(seed=4, energy_uploads=2.0, energy_spread=0.5)
    st = _state(scenario, spec)
    assert np.isfinite(st.energy_budget).all() and (st.energy_budget > 0).all()
    assert st.alive().all()
    st.debit(0, float(st.energy_remaining[0]) + 1.0)
    assert st.energy_remaining[0] == 0.0  # clamped, never negative
    assert not st.alive()[0]
    assert not st.participation(1)[0]  # dead EUs cannot participate
    # infinite budgets never die
    st_inf = _state(scenario, FaultSpec(seed=4))
    st_inf.debit(0, 1e30)
    assert st_inf.alive().all()


def test_debit_round_charges_global_client_order(scenario, lam):
    spec = FaultSpec(seed=5, energy_uploads=6.0)
    a, b = _state(scenario, spec), _state(scenario, spec)
    attempted = np.ones(len(scenario.clients), bool)
    a.debit_round(1, attempted, lam)
    b.debit_round(1, attempted, lam)
    assert np.array_equal(a.energy_remaining, b.energy_remaining)
    assert (a.energy_remaining < a.energy_budget).all()


# -- async upload-cascade planning --------------------------------------------


def test_plan_upload_clean_delivery(scenario):
    st = _state(scenario, FaultSpec(seed=6, p_fail=0.0))
    plan = st.plan_upload(1, 0, 0, latency_s=0.2)
    assert plan.ok and plan.reason == ""
    assert plan.t_end == pytest.approx(0.2)
    assert plan.windows == [(0.0, pytest.approx(0.2), 0)]
    assert plan.retries == 0


def test_plan_upload_exhausts_retries(scenario):
    st = _state(scenario, FaultSpec(seed=6, p_fail=1.0, max_retries=2,
                                    backoff_s=0.1))
    plan = st.plan_upload(1, 0, 0, latency_s=0.2)
    assert not plan.ok and plan.reason == "retries"
    assert len(plan.windows) == 3 and plan.retries == 2
    # exponential backoff between windows: 0.1, then 0.2
    (s0, e0, _), (s1, e1, _), (s2, _, _) = plan.windows
    assert s1 - e0 == pytest.approx(0.1)
    assert s2 - e1 == pytest.approx(0.2)


def test_plan_upload_timeout(scenario):
    st = _state(scenario, FaultSpec(seed=6, p_fail=1.0, max_retries=5,
                                    backoff_s=0.1, timeout_s=0.5))
    plan = st.plan_upload(1, 0, 0, latency_s=0.2)
    assert not plan.ok and plan.reason == "timeout"
    assert plan.t_end == pytest.approx(0.5)  # edge gives up at the deadline
    assert len(plan.windows) < 6
    # a deadline shorter than one airtime kills the cascade immediately
    st2 = _state(scenario, FaultSpec(seed=6, p_fail=1.0, timeout_s=0.1))
    assert st2.plan_upload(1, 0, 0, latency_s=0.2).windows == []


def test_plan_upload_energy_death_mid_cascade(scenario):
    st = _state(scenario, FaultSpec(seed=6, p_fail=1.0, max_retries=3,
                                    energy_uploads=6.0))
    st.energy_remaining[0] = 0.0
    plan = st.plan_upload(1, 0, 0, latency_s=0.2)
    assert not plan.ok and plan.reason == "energy"
    assert len(plan.windows) == 1  # attempt 0 flew; retry had no battery


def test_plan_upload_redispatch_keys_fresh_draws(scenario):
    spec = FaultSpec(seed=6, p_fail=0.5, max_retries=2)
    a, b = _state(scenario, spec), _state(scenario, spec)
    plans_a = [a.plan_upload(1, 0, 0, 0.2) for _ in range(4)]
    plans_b = [b.plan_upload(1, 0, 0, 0.2) for _ in range(4)]
    assert [p.windows for p in plans_a] == [p.windows for p in plans_b]
    assert len({len(p.windows) for p in plans_a}) > 1  # dispatches differ


# -- assignment re-repair ------------------------------------------------------


def test_repair_assignment_rehomes_infeasible_clients():
    lam = np.array([[1, 0], [0, 1], [1, 0]], dtype=float)
    counts = np.array([[4, 0], [0, 4], [2, 2]], dtype=float)
    feasible = np.array([[True, True], [True, False], [True, True]])
    new, changed = repair_assignment(lam, counts, feasible)
    assert [int(i) for i in changed] == [1]
    assert new[1, 0] == 1.0 and new[1, 1] == 0.0
    assert np.array_equal(new[0], lam[0]) and np.array_equal(new[2], lam[2])
    # nothing infeasible -> identity
    same, none = repair_assignment(lam, counts, np.ones_like(feasible, bool))
    assert len(none) == 0 and np.array_equal(same, lam)


# -- engine-level parity and determinism ---------------------------------------


def _run(scenario, lam, *, spec=None, engine="reference", seed=0, rounds=2, **kw):
    return scenario.simulate(
        lam, cloud_rounds=rounds, schedule=HFLSchedule(1, 2), seed=seed,
        engine=engine, faults=spec if spec is not None else False, **kw)


_KEYS = ("eu_up_bits", "wasted_bits", "dropped_uploads",
         "retried_uploads", "abandoned_uploads")


def test_sync_paths_agree_under_chaos(scenario, lam):
    spec = FaultSpec(seed=3, **CHAOS)
    ref = _run(scenario, lam, spec=spec)
    dev = _run(scenario, lam, spec=spec, engine="sync")
    accs = [[round(m.test_acc, 6) for m in r.history] for r in (ref, dev)]
    assert accs[0] == accs[1]
    totals = [r.accountant.totals() for r in (ref, dev)]
    for k in _KEYS:
        assert totals[0][k] == totals[1][k], k
    assert totals[0]["wasted_bits"] > 0
    assert totals[0]["dropped_uploads"] > 0


def test_chaos_run_is_deterministic_per_engine(scenario, lam):
    spec = FaultSpec(seed=9, **CHAOS)
    for engine in ("reference", "async"):
        r1 = _run(scenario, lam, spec=spec, engine=engine)
        r2 = _run(scenario, lam, spec=spec, engine=engine)
        assert _params_hash(r1.final_params) == _params_hash(r2.final_params)
        t1, t2 = r1.accountant.totals(), r2.accountant.totals()
        assert all(t1[k] == t2[k] for k in _KEYS)


def test_async_retries_and_completes_under_chaos(scenario, lam):
    spec = FaultSpec(seed=3, **CHAOS)
    res = _run(scenario, lam, spec=spec, engine="async", rounds=2)
    assert len(res.history) == 2
    t = res.accountant.totals()
    assert t["retried_uploads"] > 0
    assert t["wasted_bits"] > 0
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(res.final_params))


def test_async_survives_total_upload_loss(scenario, lam):
    """p_fail=1, no retries: every cascade abandons; edges starve and the
    degraded drain must still land every cloud round."""
    spec = FaultSpec(seed=1, p_drop=0.0, p_fail=1.0, max_retries=0,
                     backoff_s=0.01)
    res = _run(scenario, lam, spec=spec, engine="async", rounds=2)
    assert len(res.history) == 2
    t = res.accountant.totals()
    assert t["abandoned_uploads"] > 0
    assert t["retried_uploads"] == 0


def test_async_timeout_abandons_stragglers(scenario, lam):
    """A deadline shorter than any airtime: every cascade times out, the
    engine must degrade (starved edges) instead of deadlocking."""
    spec = FaultSpec(seed=4, p_drop=0.0, p_fail=0.0, max_retries=3,
                     timeout_s=1e-4)
    res = _run(scenario, lam, spec=spec, engine="async", rounds=1)
    assert len(res.history) == 1
    assert res.accountant.totals()["abandoned_uploads"] > 0


def test_sync_survives_total_upload_loss(scenario, lam):
    """All rows masked out: partial-cohort aggregation keeps the previous
    global model instead of averaging an empty set."""
    spec = FaultSpec(seed=1, p_drop=0.0, p_fail=1.0, max_retries=0)
    ref = _run(scenario, lam, spec=spec, rounds=1)
    dev = _run(scenario, lam, spec=spec, engine="sync", rounds=1)
    assert _params_hash(ref.final_params) == _params_hash(dev.final_params)
    t = ref.accountant.totals()
    assert t["dropped_uploads"] > 0 and t["wasted_bits"] > 0


def test_energy_exhaustion_shrinks_population(scenario, lam):
    spec = FaultSpec(seed=2, p_drop=0.0, energy_uploads=1.5)
    sc = scenario
    res = _run(sc, lam, spec=spec, rounds=3)
    assert len(res.history) == 3
    # rebuild the fault state the run used and replay the debits: with a
    # ~1.5-upload budget someone must be flat after 3 charged rounds
    st = _state(sc, spec)
    for b in (1, 2, 3):
        st.debit_round(b, np.ones(len(sc.clients), bool), lam)
    assert (~st.alive()).any()


def test_reassign_repairs_under_drift(scenario, lam):
    spec = FaultSpec(seed=5, p_drop=0.0, refade_rounds=1, drift_rate=0.3,
                     reassign=True)
    for engine in ("sync", "async"):
        res = _run(scenario, lam, spec=spec, engine=engine, rounds=2)
        assert len(res.history) == 2


# -- scenario-level wiring -----------------------------------------------------


def test_simulate_rejects_non_faultspec(scenario, lam):
    with pytest.raises(TypeError, match="FaultSpec"):
        scenario.simulate(lam, cloud_rounds=1, faults=123)


def test_scenario_default_and_false_override(lam):
    kw = dict(model="mlp", scale=0.02, seed=0, n_test_per_class=10)
    chaotic = build_scenario("heartbeat", faults=FaultSpec(seed=3, **CHAOS), **kw)
    plain = build_scenario("heartbeat", **kw)
    # faults=False forces fault-free even when the scenario carries a spec
    off = chaotic.simulate(lam, cloud_rounds=1, seed=0, faults=False)
    base = plain.simulate(lam, cloud_rounds=1, seed=0)
    assert _params_hash(off.final_params) == _params_hash(base.final_params)
    # faults=None (the default) picks up the scenario's spec
    on = chaotic.simulate(lam, cloud_rounds=1, seed=0)
    assert on.accountant.totals()["wasted_bits"] > 0


def test_hetero_reference_rejects_faults():
    sc = build_scenario("heartbeat", model_mix={"cnn": 12, "mlp": 6},
                        scale=0.02, seed=0, n_test_per_class=10)
    lam = sc.assign("eara-sca").lam
    with pytest.raises(ValueError, match="sync"):
        sc.simulate(lam, cloud_rounds=1, faults=FaultSpec(seed=0),
                    engine="reference")

"""Telemetry tests: span tracing, disabled-mode no-ops, engine trajectories
bit-identical with telemetry on vs off, artifact export for all three
engines, and the jit compile-count regression guard (PR 2's tiny-N
``flat_mean`` routing must not start recompiling per round again)."""
import json

import jax
import numpy as np
import pytest

from repro.core.hfl import HFLSchedule
from repro.engine import AsyncHFLEngine, BatchedSyncEngine
from repro.federated import build_scenario
from repro.federated.client import FLClient
from repro.federated.programs import CNNProgram
from repro.models.cnn1d import CNNConfig
from repro.telemetry import (
    NULL_TELEMETRY,
    CommDelta,
    Telemetry,
    coerce_telemetry,
    jit_cache_sizes,
    registered_jits,
)
from repro.telemetry.metrics import Histogram, MetricsRegistry
from repro.telemetry.report import summary_table
from repro.telemetry.trace import NULL_SPAN, Tracer


@pytest.fixture(scope="module")
def scenario():
    return build_scenario("heartbeat", scale=0.02, seed=0, n_test_per_class=20)


@pytest.fixture(scope="module")
def assignment(scenario):
    return scenario.assign("eara-sca").lam


# -- tracer ----------------------------------------------------------------
def test_span_nesting_and_parents():
    tr = Tracer()
    with tr.span("outer", kind="test") as outer:
        with tr.span("inner") as inner:
            pass
        outer.set(extra=1)
    spans = {s.name: s for s in tr.spans}
    assert spans["inner"].parent == spans["outer"].sid
    assert spans["outer"].parent is None
    assert spans["outer"].attrs == {"kind": "test", "extra": 1}
    assert spans["inner"].t0 >= spans["outer"].t0
    assert spans["inner"].t1 <= spans["outer"].t1


def test_trace_export_round_trip(tmp_path):
    tr = Tracer()
    with tr.span("a", x=1):
        pass
    tr.sim_span("up", 0.5, 1.5, client=3)
    p = tr.write_jsonl(tmp_path / "t.jsonl")
    rows = [json.loads(l) for l in p.read_text().splitlines()]
    assert {r["name"] for r in rows} == {"a", "up"}
    assert {r["track"] for r in rows} == {"wall", "sim"}
    cp = tr.write_chrome_trace(tmp_path / "t.json")
    doc = json.loads(cp.read_text())
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    # wall spans on pid 1, simulated-time spans on pid 2
    assert {e["pid"] for e in xs} == {1, 2}
    sim = next(e for e in xs if e["pid"] == 2)
    assert sim["ts"] == pytest.approx(0.5e6)
    assert sim["dur"] == pytest.approx(1.0e6)
    # process_name metadata so Perfetto labels the tracks
    assert any(e["ph"] == "M" for e in evs)


def test_null_telemetry_is_noop():
    assert NULL_TELEMETRY.span("x") is NULL_SPAN
    with NULL_TELEMETRY.span("x") as sp:
        sp.set(a=1)  # swallowed
    assert NULL_TELEMETRY.jit_cost("k", lambda: 0) is None
    assert NULL_TELEMETRY.on_round(round=1) == {}
    assert NULL_TELEMETRY.flush() == {}
    assert not NULL_TELEMETRY.enabled


def test_coerce_telemetry(tmp_path):
    assert coerce_telemetry(None) is None
    assert coerce_telemetry(False) is None
    assert coerce_telemetry(NULL_TELEMETRY) is None
    t = coerce_telemetry(True)
    assert isinstance(t, Telemetry) and t.out_dir is None
    assert coerce_telemetry(t) is t
    t2 = coerce_telemetry(str(tmp_path / "out"))
    assert t2.out_dir is not None
    with pytest.raises(TypeError):
        coerce_telemetry(42)


# -- metrics ---------------------------------------------------------------
def test_histogram_summary():
    h = Histogram()
    for v in [1.0, 2.0, 3.0, 4.0]:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["mean"] == pytest.approx(2.5)
    assert s["min"] == 1.0 and s["max"] == 4.0
    assert s["p50"] == pytest.approx(2.0, abs=1.0)


def test_metrics_registry_snapshot():
    m = MetricsRegistry()
    m.inc("n")
    m.inc("n", 2)
    m.set_gauge("g", 7.5)
    m.observe("h", 1.0)
    snap = m.snapshot()
    assert snap["counters"]["n"] == 3
    assert snap["gauges"]["g"] == 7.5
    assert snap["histograms"]["h"]["count"] == 1


def test_jit_cost_cached():
    tel = Telemetry()
    calls = []
    orig = tel._analyze

    def counting(key, fn, args, kwargs):
        calls.append(key)
        return orig(key, fn, args, kwargs)

    tel._analyze = counting
    import jax.numpy as jnp

    def f(a, b):
        return a @ b

    c1 = tel.jit_cost("mm", f, jnp.ones((4, 8)), jnp.ones((8, 2)))
    c2 = tel.jit_cost("mm", f, jnp.ones((4, 8)), jnp.ones((8, 2)))
    assert c1 == c2 and c1["flops"] == pytest.approx(2 * 4 * 8 * 2)
    assert calls == ["mm"]  # second call was a cache hit
    # a new shape re-analyzes under the same key
    tel.jit_cost("mm", f, jnp.ones((2, 8)), jnp.ones((8, 2)))
    assert calls == ["mm", "mm"]


# -- trajectories are bit-identical with telemetry on vs off ---------------
def _traj_fields(res):
    return [
        (m.cloud_round, m.test_acc, m.divergence, m.mean_local_loss)
        for m in res.history
    ]


@pytest.mark.parametrize("engine,kw", [
    ("sync", {}),
    ("async", {}),
])
def test_bit_identical_on_vs_off(scenario, assignment, engine, kw):
    r_off = scenario.simulate(assignment, 2, engine=engine, seed=0, **kw)
    r_on = scenario.simulate(assignment, 2, engine=engine, seed=0,
                             telemetry=True, **kw)
    assert r_off.telemetry is None and r_on.telemetry is not None
    assert _traj_fields(r_off) == _traj_fields(r_on)
    for a, b in zip(jax.tree.leaves(r_off.final_params),
                    jax.tree.leaves(r_on.final_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if engine == "async":  # the event clock is deterministic either way
        assert [m.sim_seconds for m in r_off.history] == \
               [m.sim_seconds for m in r_on.history]


def test_round_metrics_timing_always_on(scenario, assignment):
    """RoundMetrics timing does not need telemetry (fig5/fig6 read it)."""
    res = scenario.simulate(assignment, 1, engine="sync", seed=0)
    assert res.history[0].wall_seconds > 0.0
    res = scenario.simulate(assignment, 1, engine="async", seed=0)
    assert res.history[0].wall_seconds > 0.0
    assert res.history[0].sim_seconds > 0.0


# -- artifact export across all three engines (hetero population) ----------
@pytest.fixture(scope="module")
def hetero_scenario():
    return build_scenario(
        "heartbeat", model_mix={"cnn": 12, "mlp": 6}, scale=0.02, seed=0,
        n_test_per_class=20,
    )


@pytest.mark.parametrize("engine,kw,train_span", [
    ("reference", {}, "local_train"),
    ("sync", {}, "cohort_epoch"),
    ("async", {}, "cohort_epoch"),
])
def test_engine_artifacts(tmp_path, hetero_scenario, engine, kw, train_span):
    sc = hetero_scenario
    lam = sc.assign("eara-sca").lam
    out = tmp_path / engine
    res = sc.simulate(lam, 2, engine=engine, seed=0, telemetry=out, **kw)
    assert res.telemetry is not None
    doc = json.loads((out / "trace.json").read_text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert {
        "assignment", train_span, "edge_aggregate", "cloud_reduce",
        "kd_fuse", "eval", "cloud_round",
    } <= names
    if engine != "reference":
        # jitted-program spans carry HLO-derived analytic cost
        flops = [e for e in xs if e["name"] == train_span
                 and "flops" in e.get("args", {})]
        assert flops and flops[0]["args"]["flops"] > 0
    if engine == "async":
        assert any(e["pid"] == 2 for e in xs)  # simulated-time track
        st = res.telemetry.metrics.snapshot()["histograms"]
        assert "async_staleness" in st
    rounds = [json.loads(l) for l in (out / "rounds.jsonl").read_text().splitlines()]
    assert [r["round"] for r in rounds] == [1, 2]
    assert all(r["engine"] for r in rounds)
    assert all(r["wall_s"] > 0 for r in rounds)
    assert all(r["eu_up_bits"] > 0 for r in rounds)
    assert all("spans" in r and "jit_cache_sizes" in r for r in rounds)
    assert (out / "summary.txt").read_text().strip()
    assert "kd_loss" in res.telemetry.metrics.snapshot()["histograms"]


# -- compile-count regression guard ----------------------------------------
_GUARD_CFG = CNNConfig(in_channels=1, n_classes=5, seq_len=72, c1=6, c2=6,
                       hidden=12)


def _guard_population(m=10, n_edges=3, seed=0):
    """Population with shapes unique to this test so round 1 must compile."""
    from repro.data.partition import split_dataset_by_counts
    from repro.data.synthetic_health import heartbeat_like

    rng = np.random.default_rng(seed)
    counts = rng.integers(2, 5, (m, _GUARD_CFG.n_classes))
    train = heartbeat_like(rng, counts.sum(axis=0))
    train.x = train.x[:, : _GUARD_CFG.seq_len, : _GUARD_CFG.in_channels]
    shards = split_dataset_by_counts(rng, train, counts)
    test = heartbeat_like(rng, np.full(_GUARD_CFG.n_classes, 5))
    test.x = test.x[:, : _GUARD_CFG.seq_len, : _GUARD_CFG.in_channels]
    prog = CNNProgram(_GUARD_CFG)
    clients = [FLClient(i, shards[i], prog) for i in range(m)]
    assignment = np.zeros((m, n_edges))
    assignment[np.arange(m), np.arange(m) % n_edges] = 1.0
    return clients, assignment, test, prog


def test_compile_counts_stable_across_sync_rounds():
    """A 2-round sync-device run compiles in round 1 and NEVER recompiles in
    round 2 — the guard that locks PR 2's fixed-shape round pipeline."""
    clients, assignment, test, prog = _guard_population()
    tel = Telemetry()
    sim = BatchedSyncEngine(
        clients, assignment, prog, test, schedule=HFLSchedule(1, 1), seed=0,
        upp=1.0, telemetry=tel,
    )
    sim.run(2, eval_every=1)
    r1, r2 = (r["jit_cache_sizes"] for r in tel.rounds)
    # round 1 compiled this population's unique cohort shape ...
    assert r1.get("cohort_epoch_flat", 0) >= 1
    # ... and round 2 compiled NOTHING new, in any registered jit program
    assert r2 == r1, f"round 2 recompiled: { {k: (r1.get(k), v) for k, v in r2.items() if r1.get(k) != v} }"


def test_async_tiny_means_do_not_compile_pallas_aggregate():
    """Async quorum flushes average 1-3 rows; they must route through the
    jitted small-N contraction, not compile ``hier_aggregate`` per buffer
    size (the PR 2 ``flat_mean`` recompile fix)."""
    clients, assignment, test, prog = _guard_population(seed=1)
    rng = np.random.default_rng(3)
    latency = rng.uniform(0.01, 0.2, assignment.shape)
    before = jit_cache_sizes().get("hier_aggregate", 0)
    sim = AsyncHFLEngine(
        clients, assignment, prog, test, latency=latency,
        schedule=HFLSchedule(1, 1), seed=0, quorum=0.5,
    )
    sim.run(2, eval_every=1)
    after = jit_cache_sizes().get("hier_aggregate", 0)
    assert after - before == 0
    assert "small_mean" in registered_jits()


# -- report helpers --------------------------------------------------------
def test_comm_delta(scenario, assignment):
    res = scenario.simulate(assignment, 1, engine="sync", seed=0)
    cd = CommDelta(res.accountant)
    d1 = cd.take()
    assert d1["eu_up_bits"] == 0.0  # nothing happened since construction
    res.accountant.on_eu_exchange(0, up_bits=8.0)
    d2 = cd.take()
    assert d2["eu_up_bits"] == 8.0
    assert cd.take()["eu_up_bits"] == 0.0  # delta consumed


def test_summary_table_shape():
    rounds = [
        {"round": 1, "acc": 0.5, "loss": 0.2, "wall_s": 1.0, "sim_s": None,
         "eu_up_bits": 8e6, "eu_down_bits": 8e6, "cloud_bits": 4e6},
    ]
    txt = summary_table(rounds)
    lines = txt.splitlines()
    assert "round" in lines[0] and "acc" in lines[0]
    assert len(lines) == 3  # header, rule, one row
    assert "(no rounds recorded)" in summary_table([])


def test_simulate_flushes_to_dir(tmp_path, scenario, assignment):
    out = tmp_path / "flush"
    scenario.simulate(assignment, 1, engine="reference", seed=0, telemetry=out)
    for name in ("trace.json", "trace.jsonl", "rounds.jsonl", "metrics.json",
                 "summary.txt"):
        assert (out / name).exists(), name

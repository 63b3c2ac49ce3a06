"""The round's tail in the program's telemetry: spans mirrored into a
``jax.profiler`` trace (``Telemetry(profile=True)``), the host-to-device
byte count (``h2d_bytes``) against the shapes that are uploaded (the test
set once, when the engine builds its evaluator), the ``fetch`` spans of the
blocking reads, and the ``gc`` spans with the callback's lifetime tied to
``run()``."""
import gc
import glob
import os
import types

import jax
import numpy as np
import pytest

from repro.core.hfl import HFLSchedule
from repro.engine import BatchedSyncEngine
from repro.federated import build_scenario
from repro.telemetry import NULL_TELEMETRY, Telemetry

EDGE_ROUNDS = 2


@pytest.fixture(scope="module")
def scenario():
    return build_scenario("heartbeat", scale=0.02, seed=0, n_test_per_class=20)


@pytest.fixture(scope="module")
def assignment(scenario):
    return scenario.assign("eara-sca").lam


def _engine(scenario, assignment, tel):
    return BatchedSyncEngine(
        scenario.clients, assignment, scenario.program, scenario.test,
        schedule=HFLSchedule(1, EDGE_ROUNDS), seed=0, telemetry=tel,
    )


def _round_spans(tel, run):
    """The wall spans that ``run()`` records."""
    first = len(tel.tracer.spans)
    run()
    return [s for s in tel.tracer.spans[first:] if s.track == "wall"]


def _profiled(tmp_path, fn):
    """``fn()`` under a CPU ``jax.profiler`` trace; returns the ``/host``
    events that carry a ``sid`` stat as (name, sid, parent)."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    stats = dict(ev.stats)
                    if "sid" in stats:
                        out.append((ev.name, stats["sid"], stats.get("parent")))
    return out


@pytest.mark.parametrize("profile", [True, False])
def test_profile_mirrors_each_wall_span_once(tmp_path, scenario, assignment, profile):
    tel = Telemetry(profile=profile)
    eng = _engine(scenario, assignment, tel)
    eng.run(1)  # compile outside the trace
    spans = []
    events = _profiled(tmp_path, lambda: spans.extend(_round_spans(tel, lambda: eng.run(1))))
    if not profile:
        assert events == []
        return
    assert sorted(events) == sorted((s.name, s.sid, s.parent) for s in spans)
    assert {"cloud_round", "cohort_epoch", "eval", "fetch"} <= {e[0] for e in events}


def _shape_bytes(spans, pairs: int, edges: int, edge_rounds: int) -> dict:
    """Bytes the device round uploads, per span name, from the shapes: per
    cohort epoch span the (C,) int32 start-row ids and, per epoch, the (C,)
    cids and (C, steps, batch) int32 batch indices; per edge round the (P,)
    int32 pair gather, the (P,) float32 weights and the (E,) bool mask; per
    cloud reduce the (E,) int32 edge sizes.  The eval uploads nothing: the
    test set is on the device from the engine's construction on."""
    cohort = sum(4 * a["clients"] + a["epochs"] * 4 * a["clients"] * (1 + a["steps"] * a["batch"])
                 for a in (s.attrs for s in spans if s.name == "cohort_epoch"))
    return {
        "cohort_epoch": cohort,
        "edge_aggregate": edge_rounds * (4 * pairs + 4 * pairs + edges),
        "cloud_reduce": 4 * edges,
    }


def _test_set_bytes(test) -> int:
    """The float32 rows and int32 labels the evaluator uploads once."""
    return test.x.size * 4 + test.y.size * 4


def test_h2d_bytes_match_the_shapes_and_nothing_uploads_implicitly(scenario, assignment):
    tel = Telemetry()
    eng = _engine(scenario, assignment, tel)
    built = tel.metrics.snapshot()["counters"]
    assert built["h2d_bytes"] == _test_set_bytes(scenario.test) == 75_200
    assert built["eval_test_uploads"] == 1
    eng.run(1)
    before = tel.metrics.snapshot()["counters"]["h2d_bytes"]
    with jax.transfer_guard_host_to_device("disallow"):
        spans = _round_spans(tel, lambda: eng.run(1))
    got = {}
    for s in spans:
        if "h2d_bytes" in s.attrs:
            got[s.name] = got.get(s.name, 0) + s.attrs["h2d_bytes"]
    m, n = assignment.shape
    want = _shape_bytes(spans, int(np.count_nonzero(assignment)), n, EDGE_ROUNDS)
    assert (m, n) == (18, 5) and scenario.test.x.shape == (100, 187, 1)
    assert got == want
    assert sum(want.values()) == 25_726
    after = tel.metrics.snapshot()["counters"]
    assert after["h2d_bytes"] - before == 25_726
    assert after["eval_test_uploads"] == 1


def test_paper_round_h2d_bytes():
    """The paper's federation (Table 3: 18 EUs on 5 edges, four edge rounds,
    1,500 test samples of 187 x 1) on seed 3100000001 trains, each edge
    round, a cohort of 17 EUs at 128 steps and one of 1 EU at 16 (batch
    10): the count that ``h2d_kb_per_round`` reads for that seed.  The
    test set's 1,128,000 B count once, when the engine is built, outside
    every round."""
    cohorts = [types.SimpleNamespace(name="cohort_epoch", attrs={
        "clients": c, "epochs": 1, "steps": st, "batch": 10})
        for _ in range(4) for c, st in ((17, 128), (1, 16))]
    test = types.SimpleNamespace(x=np.zeros((1_500, 187, 1)), y=np.zeros(1_500))
    want = _shape_bytes(cohorts, 18, 5, 4)
    assert want == {"cohort_epoch": 351_296, "edge_aggregate": 596, "cloud_reduce": 20}
    assert sum(want.values()) == 351_912 == 1_479_912 - 1_128_000
    assert _test_set_bytes(test) == 1_128_000


def test_fetch_spans_are_children_of_eval_and_cloud_round(scenario, assignment):
    tel = Telemetry()
    eng = _engine(scenario, assignment, tel)
    spans = _round_spans(tel, lambda: eng.run(1))
    by_sid = {s.sid: s for s in spans}
    fetch = [s for s in spans if s.name == "fetch"]
    assert {s.attrs["what"] for s in fetch} == {"eval", "losses"}
    for s in fetch:
        want = "eval" if s.attrs["what"] == "eval" else "cloud_round"
        assert by_sid[s.parent].name == want
    # one read of the per-batch metrics, however many batches the test set has
    assert sum(s.attrs["what"] == "eval" for s in fetch) == 1


def test_gc_spans_and_round_total(scenario, assignment):
    tel = Telemetry()
    eng = _engine(scenario, assignment, tel)
    inner = eng._edge_round_device

    def collecting(edge_mats):
        gc.collect()
        return inner(edge_mats)

    eng._edge_round_device = collecting
    spans = _round_spans(tel, lambda: eng.run(1))
    pauses = [s for s in spans if s.name == "gc"]
    assert len(pauses) >= EDGE_ROUNDS
    assert {s.attrs["generation"] for s in pauses} >= {2}
    (rnd,) = [s for s in spans if s.name == "cloud_round"]
    assert rnd.attrs["gc_s"] == pytest.approx(sum(s.duration for s in pauses))
    assert all(rnd.t0 <= s.t0 and s.t1 <= rnd.t1 for s in pauses)


def test_gc_callback_lives_only_while_run_runs(scenario, assignment):
    before = list(gc.callbacks)
    tel = Telemetry()
    eng = _engine(scenario, assignment, tel)
    seen = []

    def failing(edge_mats):
        seen.append(list(gc.callbacks))
        raise RuntimeError("edge round failed")

    eng.run(1)
    assert gc.callbacks == before
    eng._edge_round_device = failing
    with pytest.raises(RuntimeError, match="edge round failed"):
        eng.run(1)
    assert len(seen[0]) == len(before) + 1
    assert gc.callbacks == before


def test_bit_identical_with_profile_on(tmp_path, scenario, assignment):
    """A profiled, telemetry-on run keeps the telemetry-off trajectory."""
    r_off = scenario.simulate(assignment, 2, engine="sync", seed=0)
    runs = []
    _profiled(tmp_path, lambda: runs.append(scenario.simulate(
        assignment, 2, engine="sync", seed=0,
        telemetry=Telemetry(profile=True))))
    (r_on,) = runs
    assert r_on.telemetry is not None
    fields = lambda r: [(m.cloud_round, m.test_acc, m.divergence, m.mean_local_loss)
                        for m in r.history]
    assert fields(r_off) == fields(r_on)
    for a, b in zip(jax.tree.leaves(r_off.final_params), jax.tree.leaves(r_on.final_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_null_telemetry_uploads_and_records_nothing():
    before = list(gc.callbacks)
    with NULL_TELEMETRY.watch_gc():
        assert gc.callbacks == before
        arr = NULL_TELEMETRY.upload(np.arange(3), np.int32)
    assert arr.dtype == np.int32 and arr.tolist() == [0, 1, 2]
    tel = Telemetry()
    tel.upload(np.zeros(4, np.float32))  # no span open: the counter alone
    assert tel.metrics.snapshot()["counters"]["h2d_bytes"] == 16
    with tel.span("outer"):
        with tel.span("inner"):
            tel.upload(np.zeros(2, np.int32))
        tel.upload(np.zeros(3, bool))
    attrs = {s.name: s.attrs for s in tel.tracer.spans}
    assert attrs == {"inner": {"h2d_bytes": 8}, "outer": {"h2d_bytes": 3}}

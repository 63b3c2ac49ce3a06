"""The reduction from a profiler trace to per-layer numbers, on a
hand-made trace whose numbers are known, laid out as
``jax.profiler.ProfileData`` lays out a TPU trace (a ``/host`` plane with
the program's annotations, ``/device:TPU:<n>`` planes with "XLA Ops" and
"XLA Modules" lines)."""
import types

import pytest

import run as bench
from tracing import TracedRun, covered, union

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _profile(planes):
    """A stand-in for ``jax.profiler.ProfileData`` over recorded events."""
    ev = lambda name, t0, dur: types.SimpleNamespace(name=name, start_ns=t0, duration_ns=dur)
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p["name"], lines=[
            types.SimpleNamespace(name=l["name"], events=[ev(*e) for e in l["events"]])
            for l in p["lines"]])
        for p in planes])


def _spans(rows):
    return [types.SimpleNamespace(name=n, t0=t0, t1=t1, track="wall", attrs=a)
            for n, t0, t1, a in rows]


def _reader(name):
    return bench.load_reader(name)


def test_union_and_cover():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert covered([(0, 10), (2, 4), (20, 25)]) == 15


def test_hand_made_trace():
    ms = 1_000_000
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["cloud_round", 0, 100 * ms], ["assignment", 0, 10 * ms],
        ["cohort_epoch", 10 * ms, 50 * ms], ["edge_aggregate", 60 * ms, 5 * ms],
        ["eval", 80 * ms, 20 * ms]]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit__cohort_epoch_flat(1)", 15 * ms, 40 * ms],
            ["jit__segment_agg_keep(2)", 66 * ms, 2 * ms]]},
        {"name": "XLA Ops", "events": [
            ["fusion.1", 15 * ms, 40 * ms], ["custom-call.1", 66 * ms, 2 * ms]]}]}
    spans = _spans([
        ("cloud_round", 0.0, 0.1, {}), ("assignment", 0.0, 0.01, {}),
        ("cohort_epoch", 0.01, 0.06, {"clients": 18, "epochs": 1, "steps": 128, "batch": 10}),
        ("edge_aggregate", 0.06, 0.065, {"clients": 18, "edges": 5}),
        ("eval", 0.08, 0.1, {})])
    cfg = {"n_params": 25_141, "widths": {"in_channels": 1, "n_classes": 5, "seq_len": 187,
                                          "c1": 16, "c2": 16, "hidden": 32, "kernel": 5}}
    run = TracedRun.from_profile(_profile([host, dev]), spans, 1, cfg, PEAKS)
    assert run.window_s == pytest.approx(0.1)
    assert run.busy_s == pytest.approx(0.042)
    assert _reader("device_idle_share")(run) == pytest.approx(58.0)
    assert _reader("host_draw_ms")(run) == pytest.approx(10.0)
    assert _reader("cohort_epoch_device_ms")(run) == pytest.approx(40.0)
    least = 4 * (18 + 10) * 25_141 / 819e9
    assert _reader("edge_aggregate_roofline")(run) == pytest.approx(100 * least / 0.002)
    samples = 18 * 128 * 10
    assert _reader("train_mfu")(run) == pytest.approx(100 * samples * 3 * 315_424 / (0.1 * 197e12))
    bd = run.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(0.04)]
    idle = dict(bd["idle_gaps"])
    assert idle == pytest.approx({"assignment": 0.01, "cohort_epoch": 0.01,
                                  "edge_aggregate": 0.005, "cloud_round": 0.013, "eval": 0.02})


def test_device_plane_without_op_lines_is_an_error():
    ms = 1_000_000
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["cloud_round", 0, 10 * ms]]}]}
    dev = {"name": "/device:TPU:0", "lines": [{"name": "Steps", "events": [
        ["a", 0, 10 * ms]]}]}
    other = {"name": "/device:TPU:0 SparseCore 0", "lines": [
        {"name": "XLA Ops", "events": [["c", 0, 10 * ms]]},
        {"name": "XLA Modules", "events": [["m", 0, 10 * ms]]}]}
    with pytest.raises(RuntimeError, match="no 'XLA Ops' line for TPU 0"):
        TracedRun.from_profile(_profile([host, dev, other]),
                               _spans([("cloud_round", 0.0, 0.01, {})]), 1, {}, PEAKS)

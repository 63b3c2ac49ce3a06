"""The readers of the round's tail (``eval_ms``, ``eval_idle_ms``,
``h2d_kb_per_round``, ``gc_ms``) on a hand-made trace whose numbers are
known, laid out as ``jax.profiler.ProfileData`` lays out a TPU trace."""
import types

import pytest

import run as bench
from tracing import TracedRun

MS = 1_000_000  # ns
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _profile(planes):
    ev = lambda name, t0, dur: types.SimpleNamespace(name=name, start_ns=t0, duration_ns=dur)
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p["name"], lines=[
            types.SimpleNamespace(name=l["name"], events=[ev(*e) for e in l["events"]])
            for l in p["lines"]])
        for p in planes])


def _spans(rows):
    return [types.SimpleNamespace(name=n, t0=t0, t1=t1, track="wall", attrs=a)
            for n, t0, t1, a in rows]


def _two_rounds(attrs=True):
    """Two 100 ms rounds; each round's ``eval`` (60-100 ms) overlaps device
    ops for 5 ms (round 1) and 15 ms (round 2), one op half outside it."""
    host_events, rows = [], []
    for r, (t, ops_in_eval) in enumerate([(0, 5), (100, 15)]):
        cr = {"gc_s": 0.002 * r} if attrs else {}
        h2d = (lambda n: {"h2d_bytes": n}) if attrs else (lambda n: {})
        for name, a, b, at in [("cloud_round", t, t + 100, cr),
                               ("cohort_epoch", t + 10, t + 50, h2d(92_381)),
                               ("edge_aggregate", t + 50, t + 55, h2d(149)),
                               ("cloud_reduce", t + 55, t + 60, h2d(20)),
                               ("eval", t + 60, t + 100, h2d(1_128_000))]:
            host_events.append([name, a * MS, (b - a) * MS])
            rows.append((name, a / 1e3, b / 1e3, at))
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": host_events}]}
    ops = [["fusion.1", 15 * MS, 30 * MS], ["fusion.2", 58 * MS, 7 * MS],  # 5 ms in eval
           ["fusion.1", 115 * MS, 30 * MS], ["fusion.3", 170 * MS, 15 * MS]]  # 15 ms in eval
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": ops}, {"name": "XLA Ops", "events": ops}]}
    return TracedRun.from_profile(_profile([host, dev]), _spans(rows), 1, {}, PEAKS)


def test_round_tail_readers():
    run = _two_rounds()
    assert run.rounds == 2
    assert bench.load_reader("eval_ms")(run) == pytest.approx(40.0)
    # eval open 2 x 40 ms, the chip busy 5 + 15 ms of it: 60 ms idle over 2 rounds
    assert bench.load_reader("eval_idle_ms")(run) == pytest.approx(30.0)
    assert bench.load_reader("h2d_kb_per_round")(run) == pytest.approx(
        (92_381 + 149 + 20 + 1_128_000) / 1e3)
    assert bench.load_reader("gc_ms")(run) == pytest.approx(1.0)  # 0 + 2 ms over 2 rounds


def test_eval_idle_agrees_with_the_breakdown():
    run = _two_rounds()
    idle = dict(run.breakdown()["idle_gaps"])["eval"]
    assert bench.load_reader("eval_idle_ms")(run) * run.rounds / 1e3 == pytest.approx(idle)


def test_attributes_a_program_lacks_read_nothing():
    """A program without the upload counter or the GC spans (one that
    predates them) leaves those metrics out; its eval spans still read."""
    run = _two_rounds(attrs=False)
    assert bench.load_reader("h2d_kb_per_round")(run) is None
    assert bench.load_reader("gc_ms")(run) is None
    assert bench.load_reader("eval_ms")(run) == pytest.approx(40.0)

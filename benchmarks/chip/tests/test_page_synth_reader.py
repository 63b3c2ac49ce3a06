"""``page_synth_ms`` on a hand-made trace whose numbers are known: two
100 ms rounds of two cohort epochs each, laid out as
``jax.profiler.ProfileData`` lays out a TPU trace."""
import pytest

import run as bench
from test_round_tail_readers import MS, PEAKS, _profile, _spans
from tracing import TracedRun


def _rounds(synth=True):
    """Each epoch pages in for 20 ms, 12 ms of it synthesis (8 ms in the
    second round); one epoch outside the window."""
    host_events, rows = [], []

    def epoch(synth_s):
        a = {"clients": 100, "epochs": 1, "steps": 1, "batch": 10,
             "page_in_s": 0.020, "page_misses": 100, "page_hits": 0,
             "page_in_bytes": 1_000_000}
        if synth:
            a["page_synth_s"] = synth_s
        return a

    for r, t in enumerate([0, 100]):
        synth_s = 0.012 if r == 0 else 0.008
        for name, a, b, at in [("cloud_round", t, t + 100, {}),
                               ("cohort_epoch", t + 5, t + 40, epoch(synth_s)),
                               ("cohort_epoch", t + 40, t + 80, epoch(synth_s)),
                               ("eval", t + 85, t + 100, {})]:
            host_events.append([name, a * MS, (b - a) * MS])
            rows.append((name, a / 1e3, b / 1e3, at))
    host_events.append(["cohort_epoch", 250 * MS, 10 * MS])  # after the last round
    rows.append(("cohort_epoch", 0.25, 0.26, epoch(9.0)))
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": host_events}]}
    ops = [["fusion.1", 50 * MS, 20 * MS], ["fusion.1", 150 * MS, 20 * MS]]
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": ops}, {"name": "XLA Ops", "events": ops}]}
    return TracedRun.from_profile(_profile([host, dev]), _spans(rows), 1, {}, PEAKS)


def test_page_synth_ms_reads_the_window_synthesis():
    run = _rounds()
    assert run.rounds == 2
    # 2 epochs x (12 + 8) ms over 2 rounds, inside page_in_ms's 40 ms
    assert bench.load_reader("page_synth_ms")(run) == pytest.approx(20.0)
    assert bench.load_reader("page_in_ms")(run) == pytest.approx(40.0)


def test_a_store_without_the_synthesis_attribute_reads_nothing():
    """A program whose ``page_in`` span predates ``synth_s`` leaves the
    metric out; page-in itself still reads."""
    run = _rounds(synth=False)
    assert bench.load_reader("page_synth_ms")(run) is None
    assert bench.load_reader("page_in_ms")(run) == pytest.approx(40.0)

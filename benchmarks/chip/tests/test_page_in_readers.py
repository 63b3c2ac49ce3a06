"""The streaming fleet's readers (``page_in_ms``, ``page_in_mb_per_round``,
``fleet_train_mfu``) on a hand-made trace whose numbers are known, laid
out as ``jax.profiler.ProfileData`` lays out a TPU trace."""
import pytest

import run as bench
from flops import cnn_train_flops
from test_round_tail_readers import MS, PEAKS, _profile, _spans
from tracing import TracedRun

WIDTHS = {"in_channels": 19, "n_classes": 3, "seq_len": 178, "c1": 16, "c2": 16,
          "hidden": 32, "kernel": 5}


def _fleet_rounds(attrs=True):
    """Two 100 ms rounds of two cohort epochs each (1 step and 2 steps of
    batch 10, 700 and 1,300 clients); each epoch paged in for 20 ms (10 ms
    in the second round's) and uploaded 1 MB; one epoch outside the window."""
    host_events, rows = [], []

    def epoch(t, steps, clients, page_s):
        a = {"clients": clients, "epochs": 1, "steps": steps, "batch": 10}
        if attrs:
            a.update(page_in_s=page_s, page_misses=clients, page_hits=0,
                     page_in_bytes=1_000_000)
        return a

    for r, t in enumerate([0, 100]):
        page_s = 0.020 if r == 0 else 0.010
        for name, a, b, at in [("cloud_round", t, t + 100, {}),
                               ("cohort_epoch", t + 5, t + 40, epoch(t, 1, 700, page_s)),
                               ("cohort_epoch", t + 40, t + 80, epoch(t, 2, 1300, page_s)),
                               ("eval", t + 85, t + 100, {})]:
            host_events.append([name, a * MS, (b - a) * MS])
            rows.append((name, a / 1e3, b / 1e3, at))
    host_events.append(["cohort_epoch", 250 * MS, 10 * MS])  # after the last round
    rows.append(("cohort_epoch", 0.25, 0.26, epoch(250, 1, 5, 9.0)))
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": host_events}]}
    ops = [["fusion.1", 50 * MS, 20 * MS], ["fusion.1", 150 * MS, 20 * MS]]
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": ops}, {"name": "XLA Ops", "events": ops}]}
    return TracedRun.from_profile(_profile([host, dev]), _spans(rows), 1, {"widths": WIDTHS},
                                  PEAKS)


def test_page_in_readers():
    run = _fleet_rounds()
    assert run.rounds == 2 and run.window_s == pytest.approx(0.2)
    # 2 epochs x (20 + 10) ms over 2 rounds
    assert bench.load_reader("page_in_ms")(run) == pytest.approx(30.0)
    assert bench.load_reader("page_in_mb_per_round")(run) == pytest.approx(2.0)


def test_fleet_train_mfu_counts_the_window_samples():
    samples = 2 * (700 * 1 * 10 + 1300 * 2 * 10)
    want = 100.0 * samples * cnn_train_flops(WIDTHS) / (0.2 * PEAKS["bf16_flops"])
    run = _fleet_rounds()
    assert bench.load_reader("fleet_train_mfu")(run) == pytest.approx(want)
    assert bench.load_reader("fleet_train_mfu")(run) == bench.load_reader("train_mfu")(run)


def test_a_store_without_page_in_spans_reads_nothing():
    """A program that predates the ``page_in`` span leaves both page-in
    metrics out; the utilisation still reads."""
    run = _fleet_rounds(attrs=False)
    assert bench.load_reader("page_in_ms")(run) is None
    assert bench.load_reader("page_in_mb_per_round")(run) is None
    assert bench.load_reader("fleet_train_mfu")(run) > 0

"""``correct`` on the CPU, at a size a test can hold: a sound run passes,
the bfloat16 control and a broken timed path do not.

Each test drives the harness's own ``measure()`` (the look for a chip
skipped, the traffic cut down) or its reference, against the limits in
``limits/<cell>.json``.
"""
import json

import jax
import jax.numpy as jnp
import pytest

import compare
import reference
import run as bench

SEED = 2**31 + 11
TINY = {"heartbeat-paper": {"scale": 0.05, "test_per_class": 20}}
CELLS = [w["name"] for w in json.loads((bench.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _tiny_cell(workload):
    cell = bench.load_cell(workload)
    cell["traffic"] = dict(cell["traffic"], **TINY[workload])
    return cell


def _run(workload):
    return bench.measure(_tiny_cell(workload), jax.devices()[:1], SEED, 1.0, False)


def _frozen_edges(monkeypatch):
    """Every edge round returns the edge models it was given."""
    monkeypatch.setattr("repro.engine.sync_sim._segment_agg_keep",
                        lambda upd, seg, w, has, prev, n, backend: prev)


def _half_batch(monkeypatch):
    """Every local step takes its mean loss over half of its batch."""
    import repro.engine.sync_sim as sync_sim

    orig = sync_sim._cohort_epoch_flat

    def half(flat, xb, yb, *args):
        h = xb.shape[2] // 2
        return orig(flat, xb[:, :, :h], yb[:, :, :h], *args)

    monkeypatch.setattr(sync_sim, "_cohort_epoch_flat", half)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    line = _run(workload)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", [_frozen_edges, _half_batch], ids=["frozen", "half_batch"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, workload, fault):
    fault(monkeypatch)
    assert not _run(workload)["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_bf16_control_is_not_correct(workload):
    cell = _tiny_cell(workload)
    driver = bench.load_driver(cell["traffic"]["driver"])(cell["config"], cell["traffic"], SEED)
    fed = driver.federation()
    ref = reference.run_calls(fed, SEED, compare.CALLS)
    control = reference.run_calls(fed, SEED, compare.CALLS, dtype=jnp.bfloat16)
    checks = compare.judge(compare.numbers(control, ref), cell["limits"]["limits"])
    assert not all(c["ok"] for c in checks.values()), checks

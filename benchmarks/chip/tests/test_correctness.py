"""``correct`` on the CPU, at a size a test can hold: a sound run passes,
the bfloat16 control and a broken timed path do not.

Each test drives the harness's own ``measure()`` (the look for a chip
skipped, the traffic cut down) or its reference, against the limits in
``limits/<cell>.json``.  A cell's CPU cut, the traffic keys it overrides,
is ``cuts/<cell>.json``.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import compare
import reference
import run as bench
from engine_faults import frozen_edges, half_batch

SEED = 2**31 + 11
CUTS = Path(__file__).resolve().parent / "cuts"
CELLS = [w["name"] for w in json.loads((bench.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _tiny_cell(workload):
    cut = CUTS / f"{workload}.json"
    if not cut.exists():
        pytest.fail(f"cell {workload!r} has no CPU cut: add {cut}")
    cell = bench.load_cell(workload)
    cell["traffic"] = dict(cell["traffic"], **json.loads(cut.read_text()))
    return cell


def _run(workload):
    return bench.measure(_tiny_cell(workload), jax.devices()[:1], SEED, 1.0, False)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    line = _run(workload)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", [frozen_edges, half_batch], ids=["frozen", "half_batch"])
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

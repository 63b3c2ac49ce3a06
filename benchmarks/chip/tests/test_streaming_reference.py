"""The reference follows a sampled cohort on an engine that resumes.

A streaming stand-in (not a cell of ``BENCHMARK.json``): the lazy seizure
population, ``StreamSyncEngine`` training a uniform cohort each edge round,
driven through the harness's own ``measure()`` (its ``warm_up`` and
``reference.run_calls``) and judged by ``limits/heartbeat-paper.json``.
The stand-in's federation takes shards from the program's source and
cohorts from the program's ``CohortSpec.draw``, which a test may do and a
cell's driver may not.  Also: the reference's cohort path at a million
clients, and its full-participation path pinned to the bit.
"""
import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import gen
import reference
import run as bench
from engine_faults import frozen_edges, half_batch

SEED = 2**31 + 11
LIMITS = json.loads((bench.HERE / "limits" / "heartbeat-paper.json").read_text())
STAND_IN = {"dataset": "seizure", "n_eus": 4096, "n_edges": 8, "cohort": 64,
            "local_epochs": 1, "edge_rounds": 2, "batch": 10, "lr": 0.001,
            "max_steps": 128, "test_per_class": 20}


def _seizure_cnn() -> reference.CNN:
    from repro.models.cnn1d import SEIZURE_CNN as c

    return reference.CNN(c.in_channels, c.n_classes, c.seq_len, c.c1, c.c2, c.hidden, c.kernel)


class StreamStandIn:
    """``build_scenario(lazy=True)`` and ``StreamSyncEngine`` over a
    ``CohortSpec(cohort, "uniform")``; the engine's ``run`` resumes."""

    resumes = True

    def __init__(self, cfg: dict, traffic: dict, seed: int, telemetry=None):
        from repro.core.hfl import HFLSchedule
        from repro.engine.stream_sim import StreamSyncEngine
        from repro.federated import build_scenario
        from repro.federated.sampling import CohortSpec

        t = self.traffic = traffic
        self.sc = build_scenario(t["dataset"], lazy=True, n_eus=t["n_eus"],
                                 n_edges=t["n_edges"], seed=seed,
                                 n_test_per_class=t["test_per_class"])
        self.spec = CohortSpec(t["cohort"], "uniform", seed=seed)
        self.engine = StreamSyncEngine(
            self.sc.source, self.sc.edge_of, self.sc.program, self.sc.test,
            cohort=self.spec, n_edges=t["n_edges"],
            schedule=HFLSchedule(t["local_epochs"], t["edge_rounds"]), seed=seed,
            batch_size=t["batch"], lr=t["lr"], max_steps=t["max_steps"],
            telemetry=telemetry)

    def federation(self) -> reference.Federation:
        t, src = self.traffic, self.sc.source

        def shard(cid):
            d = src.shard(int(cid))
            return d.x, d.y

        return reference.Federation(
            cfg=_seizure_cnn(), shard=shard, sizes=np.asarray(src.sizes),
            edge_of=self.sc.edge_of, n_edges=t["n_edges"], edge_rounds=t["edge_rounds"],
            epochs=t["local_epochs"], batch=t["batch"], max_steps=t["max_steps"],
            precision="default", resumes=self.resumes,
            members=lambda b, er: self.spec.draw(b, er, eligible=None, m=src.n_clients))


class AssumesRestart(StreamStandIn):
    """The harness's old assumption: every call starts at the initial model."""

    resumes = False


def _measure(monkeypatch, driver_cls) -> dict:
    cell = {"workload": {"name": "stream-stand-in", "chips": 1}, "config": {},
            "traffic": dict(STAND_IN, driver="stand-in"), "limits": LIMITS,
            "end_to_end": [{"name": "cloud_round_ms", "unit": "ms"}], "per_layer": []}
    monkeypatch.setattr(bench, "load_driver", lambda name: driver_cls)
    return bench.measure(cell, jax.devices()[:1], SEED, 1.0, False)


def test_stand_in_sound_run_is_correct(monkeypatch):
    line = _measure(monkeypatch, StreamStandIn)
    assert line["correct"], line["checks"]
    assert line["checks"]["init_gap"]["value"] == 0.0


@pytest.mark.parametrize("fault", [frozen_edges, half_batch], ids=["frozen", "half_batch"])
def test_stand_in_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    line = _measure(monkeypatch, StreamStandIn)
    assert not line["correct"], line["checks"]


def test_stand_in_restart_assumption_is_not_correct(monkeypatch):
    line = _measure(monkeypatch, AssumesRestart)
    assert not line["correct"], line["checks"]


def test_stand_in_bf16_control_is_not_correct():
    fed = StreamStandIn({}, STAND_IN, SEED).federation()
    ref = reference.run_calls(fed, SEED, compare.CALLS)
    control = reference.run_calls(fed, SEED, compare.CALLS, dtype=jnp.bfloat16)
    checks = compare.judge(compare.numbers(control, ref), LIMITS["limits"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_stand_in_half_clients_fault_trains_every_other_member():
    """The planted fault reaches the cohort path: each edge round trains
    every other member of its cohort, and the models move off the sound
    reference's.  (Whether limits catch it is a cell's calibration: on this
    seed it reads under heartbeat-paper's.)"""
    fed = StreamStandIn({}, STAND_IN, SEED).federation()
    trained = []

    def shard(cid):
        trained.append(int(cid))
        return fed.shard(cid)

    faulted = reference.run_calls(dataclasses.replace(fed, shard=shard), SEED, compare.CALLS,
                                  fault="half_clients")
    cohorts = [fed.members(b, er) for r in compare.CALLS for b in range(1, r + 1)
               for er in range(1, fed.edge_rounds + 1)]
    assert trained == [int(c) for ids in cohorts for c in ids[::2] if fed.sizes[c]]
    nums = compare.numbers(faulted, reference.run_calls(fed, SEED, compare.CALLS))
    assert nums["update_gap"] > 0 and nums["change_gap"] > 0, nums


class ElementsOnly:
    """An (M,) array that allows single-element reads and nothing else, so
    work over the whole array raises."""

    def __init__(self, a: np.ndarray):
        self.a, self.reads = a, 0

    def __len__(self):
        return len(self.a)

    def __getitem__(self, i):
        if isinstance(i, (int, np.integer)):
            self.reads += 1
            return self.a[i]
        if isinstance(i, np.ndarray) and i.ndim == 1 and len(i) <= STAND_IN["cohort"]:
            self.reads += len(i)
            return self.a[i]
        raise TypeError(f"read of {type(i).__name__} over the whole array")


def test_cohort_reference_work_follows_the_cohort_at_a_million_clients():
    from repro.federated import build_scenario
    from repro.federated.sampling import CohortSpec

    m, n_edges = 1_000_000, 32
    sc = build_scenario("seizure", lazy=True, n_eus=m, n_edges=n_edges, seed=SEED,
                        n_test_per_class=1)
    spec = CohortSpec(STAND_IN["cohort"], "uniform", seed=SEED)
    sizes = np.asarray(sc.source.sizes)
    trained = []

    def shard(cid):
        trained.append(int(cid))
        d = sc.source.shard(int(cid))
        return d.x, d.y

    whole = reference.Federation(
        cfg=_seizure_cnn(), shard=shard, sizes=sizes, edge_of=sc.edge_of, n_edges=n_edges,
        edge_rounds=2, epochs=1, batch=10, max_steps=128, precision="default",
        members=lambda b, er: spec.draw(b, er, eligible=None, m=m), resumes=True)
    fed = dataclasses.replace(whole, sizes=ElementsOnly(sizes), edge_of=ElementsOnly(sc.edge_of))
    out = reference.run_calls(fed, SEED, (1, 1))
    cohorts = [spec.draw(b, er, eligible=None, m=m) for b in (1, 1) for er in (1, 2)]
    assert trained == [int(c) for ids in cohorts for c in ids if sizes[c]]
    assert fed.sizes.reads + fed.edge_of.reads <= 4 * len(trained)
    np.testing.assert_array_equal(fed.cloud_weights, whole.cloud_weights)
    for a, b in zip(jax.tree.leaves(out[0]["end"]), jax.tree.leaves(out[1]["start"])):
        np.testing.assert_array_equal(a, b)  # the second call resumes
    assert all(np.isfinite(l) for c in out for l in c["losses"])


# Digests of ``reference.run_calls`` on the table-3 federation below, read
# from the reference as it was before it could follow a cohort.
PINNED = {
    "sound": "6c7f8dd0f3dd43f79cff0c90b48c6026cfa275326c8dcbcd563dfd794f6d8fdc",
    "half_clients": "8067712895745bcb29f079966b8d638a4b8b81ba7924185590cddb511b698dbd",
    "control_bf16": "a6f19f74001970fbf570d8a61ceaec98cbb8bdfa33ba1469fc4055e4365612a3",
}
PINNED_RUNS = {"sound": {}, "half_clients": dict(fault="half_clients"),
               "control_bf16": dict(dtype=jnp.bfloat16)}


def _digest(calls) -> str:
    h = hashlib.sha256()
    for c in calls:
        for part in ("start", "end"):
            for leaf in jax.tree.leaves(c[part]):
                h.update(np.ascontiguousarray(leaf, np.float64).tobytes())
        h.update(np.asarray(c["losses"], np.float64).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("kind", list(PINNED))
def test_full_participation_reference_is_pinned(kind):
    w = json.loads((bench.HERE / "configs" / "cnn-heartbeat.json").read_text())["widths"]
    shards, _ = gen.table3_population(SEED, 0.05)
    fed = reference.Federation(
        cfg=reference.CNN(w["in_channels"], w["n_classes"], w["seq_len"], w["c1"], w["c2"],
                          w["hidden"], w["kernel"]),
        shard=lambda c: (shards[c].x, shards[c].y), sizes=np.array([len(s) for s in shards]),
        edge_of=np.arange(len(shards)) % 5, n_edges=5, edge_rounds=4, epochs=1, batch=10,
        max_steps=128, precision="default")
    assert _digest(reference.run_calls(fed, SEED, compare.CALLS, **PINNED_RUNS[kind])) == PINNED[kind]

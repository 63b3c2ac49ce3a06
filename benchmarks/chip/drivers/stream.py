"""The driver of the streaming fleet, filled in by a traffic file.

Builds the lazy population through ``build_scenario(lazy=True)`` and
``StreamSyncEngine`` over a uniform ``CohortSpec``, checks what the
program built against the configuration and the traffic file, and
describes the same fleet to the plain reference from the benchmark's own
copies of the keyed shard source and the cohort draw (``gen_fleet``).
"""
from __future__ import annotations

import runpy
from pathlib import Path

import numpy as np

import gen_fleet
from reference import Federation

_eager = runpy.run_path(str(Path(__file__).with_name("eager.py")))


def _check_source(source, cfg: dict, traffic: dict) -> None:
    w = cfg["widths"]
    want = (traffic["n_eus"], w["n_classes"], w["seq_len"], w["in_channels"],
            traffic["min_per_class"], traffic["max_per_class"], traffic["dom_boost"])
    got = tuple(getattr(source, k, None) for k in (
        "n_clients", "n_classes", "length", "channels", "min_per_class", "max_per_class",
        "dom_boost"))
    if got != want:
        raise ValueError(f"the scenario's shard source {got} is not the traffic file's {want}")


def _check_engine(engine, traffic: dict) -> None:
    c, p = engine.cohort, engine.plan
    got = (c.size, c.strategy, p.batch, p.lr, p.max_steps, engine.n_edges,
           engine.store.capacity)
    want = (traffic["cohort"], traffic["strategy"], traffic["batch"], traffic["lr"],
            traffic["max_steps"], traffic["n_edges"], 2 * traffic["cohort"])
    if got != want:
        raise ValueError(f"the engine {got} is not the traffic file's {want}")


class Driver:
    """A lazy fleet, striped over its edges, and ``StreamSyncEngine``
    training a uniform cohort each edge round."""

    resumes = True  # StreamSyncEngine.run resumes from the last call's model

    def __init__(self, cfg: dict, traffic: dict, seed: int, telemetry=None):
        from repro.engine.stream_sim import StreamSyncEngine
        from repro.federated import build_scenario
        from repro.federated.sampling import CohortSpec
        from repro.federated.stream import striped_assignment

        t = traffic
        self.cfg, self.traffic, self.seed = cfg, t, seed
        if t["strategy"] != "uniform":
            raise ValueError(f"the reference draws uniform cohorts only, not {t['strategy']!r}")
        sc = build_scenario(t["dataset"], lazy=True, n_eus=t["n_eus"], n_edges=t["n_edges"],
                            seed=seed, n_test_per_class=t["test_per_class"])
        _eager["_check_program"](sc.program, cfg)
        _check_source(sc.source, cfg, t)
        self.edge_of = np.asarray(sc.edge_of)
        if not np.array_equal(self.edge_of, striped_assignment(sc.source, t["n_edges"], t["assign"])):
            raise ValueError(f"the scenario's assignment is not {t['assign']!r}")
        self.engine = StreamSyncEngine(
            sc.source, sc.edge_of, sc.program, sc.test,
            cohort=CohortSpec(t["cohort"], t["strategy"], seed=seed), n_edges=t["n_edges"],
            schedule=_eager["_schedule"](t), seed=seed, batch_size=t["batch"], lr=t["lr"],
            max_steps=t["max_steps"], telemetry=telemetry)
        _check_engine(self.engine, t)

    def federation(self) -> Federation:
        t, w = self.traffic, self.cfg["widths"]
        m, n = t["n_eus"], t["n_edges"]
        eo = self.edge_of
        if eo.shape != (m,) or not np.issubdtype(eo.dtype, np.integer) or eo.min() < 0 \
                or eo.max() >= n:
            raise ValueError("the assignment does not attach every client to one edge")
        fleet = gen_fleet.HealthFleet(
            self.seed, m, w["n_classes"], w["seq_len"], w["in_channels"], t["min_per_class"],
            t["max_per_class"], t["dom_boost"])

        def shard(cid):
            s = fleet.shard(cid)
            return s.x, s.y

        return Federation(
            cfg=_eager["_ref_cnn"](self.cfg), shard=shard, sizes=fleet.sizes(), edge_of=eo,
            n_edges=n, edge_rounds=t["edge_rounds"], epochs=t["local_epochs"],
            batch=t["batch"], max_steps=t["max_steps"],
            precision=self.cfg["train_matmul_precision"], resumes=self.resumes,
            members=lambda b, er: gen_fleet.uniform_cohort(self.seed, t["cohort"], m, b, er))

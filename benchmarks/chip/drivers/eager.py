"""The driver of the materialised federation, filled in by a traffic file.

A driver (``drivers/<name>.py``, named by a traffic file's ``"driver"``)
builds the system under test through the program's own entry points
(scenario builder, assignment, engine constructor), exposes the engine whose
``run`` the timed window calls, states in ``resumes`` whether that ``run``
starts from the previous call's end model (else from the initial model),
and describes the same federation to the plain reference from the
benchmark's own generators (``gen``), never from what the program made.
"""
from __future__ import annotations

import numpy as np

import gen
from reference import CNN, Federation


def _program_cfg(cfg: dict):
    from repro.models.cnn1d import CNNConfig

    return CNNConfig(**{k: cfg["widths"][k] for k in (
        "in_channels", "n_classes", "seq_len", "c1", "c2", "hidden", "kernel")})


def _ref_cnn(cfg: dict) -> CNN:
    w = cfg["widths"]
    return CNN(w["in_channels"], w["n_classes"], w["seq_len"], w["c1"], w["c2"],
               w["hidden"], w["kernel"])


def _check_program(program, cfg: dict) -> None:
    want = _program_cfg(cfg)
    got = getattr(program, "cfg", None)
    if got != want:
        raise ValueError(f"the scenario's client program {got} is not the configuration {want}")


def _check_clients(clients, traffic: dict) -> None:
    for c in clients:
        if (c.batch_size, c.lr, c.max_steps, c.local_epochs) != (
                traffic["batch"], traffic["lr"], traffic["max_steps"], None):
            raise ValueError(f"client {c.cid} trains with other hyperparameters than the traffic file")


def _schedule(traffic: dict):
    from repro.core.hfl import HFLSchedule

    return HFLSchedule(traffic["local_epochs"], traffic["edge_rounds"])


class Driver:
    """The paper's federation: ``build_scenario`` (materialised shards), the
    assignment it names, and ``BatchedSyncEngine``'s device pipeline."""

    resumes = False  # BatchedSyncEngine.run restarts from the initial model

    def __init__(self, cfg: dict, traffic: dict, seed: int, telemetry=None):
        from repro.engine import BatchedSyncEngine
        from repro.federated import build_scenario

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        sc = build_scenario(traffic["dataset"], scale=traffic["scale"], seed=seed,
                            n_test_per_class=traffic["test_per_class"])
        _check_program(sc.program, cfg)
        _check_clients(sc.clients, traffic)
        self.lam = np.asarray(sc.assign(traffic["assignment"]).lam)
        self.engine = BatchedSyncEngine(
            sc.clients, self.lam, sc.program, sc.test, schedule=_schedule(traffic),
            seed=seed, upp=1.0, pipeline="device", telemetry=telemetry)

    def federation(self) -> Federation:
        t = self.traffic
        if not (self.lam.sum(axis=1) == 1).all():
            raise ValueError("the assignment is not single-connectivity (one edge per EU)")
        shards, _ = gen.table3_population(self.seed, t["scale"])
        n = self.lam.shape[1]
        return Federation(
            cfg=_ref_cnn(self.cfg), shard=lambda c: (shards[c].x, shards[c].y),
            sizes=np.array([len(s) for s in shards]), edge_of=self.lam.argmax(axis=1),
            n_edges=n, edge_rounds=t["edge_rounds"], epochs=t["local_epochs"],
            batch=t["batch"], max_steps=t["max_steps"],
            precision=self.cfg["train_matmul_precision"], resumes=self.resumes)

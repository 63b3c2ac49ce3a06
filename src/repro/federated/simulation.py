"""Synchronous hierarchical FL simulation (the paper's Sec. 6 experiments).

Drives M clients, N edge nodes, and a central server through the two-level
aggregation schedule; tracks accuracy vs cloud rounds, weight divergence to
the virtual-centralized model (eq. 17), and communication traffic — the raw
material of paper Figs. 3-6.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressionSpec
from repro.core.hfl import CommAccountant, HFLSchedule, WallClock, cloud_aggregate, edge_aggregate, weight_divergence
from repro.data.synthetic_health import Dataset
from repro.federated.client import FLClient, _local_epoch
from repro.federated.programs import as_program, group_clients, group_edge_sizes
from repro.telemetry import NULL_TELEMETRY, coerce_telemetry, register_jit
from repro.telemetry.report import CommDelta
from repro.utils.tree import tree_add, tree_size_bytes, tree_sub


@dataclasses.dataclass
class RoundMetrics:
    cloud_round: int
    test_acc: float
    divergence: float
    mean_local_loss: float
    # timing is always on (nanosecond-cost counters, no telemetry needed):
    # host seconds spent since the previous history entry, and — when the
    # run models latency (WallClock / the async EventQueue) — the simulated
    # seconds that elapsed over the same rounds
    wall_seconds: float = 0.0
    sim_seconds: float = 0.0


@dataclasses.dataclass
class SimResult:
    history: List[RoundMetrics]
    accountant: CommAccountant
    final_params: dict
    wall_seconds: float = 0.0
    # the run's Telemetry object (None when telemetry was disabled):
    # `.summary()` is the end-of-run table, `.rounds` the per-round records
    telemetry: object = None
    # per-round serve records when the run carried query traffic
    # (Scenario.simulate(serve=TrafficSpec(...))): one dict per cloud round
    # with round / queries / serve_qps / serve_staleness_rounds / serve_acc
    serve_history: Optional[List[dict]] = None

    def rounds_to_accuracy(self, target: float) -> Optional[int]:
        for m in self.history:
            if m.test_acc >= target:
                return m.cloud_round
        return None

    def final_accuracy(self) -> float:
        return self.history[-1].test_acc if self.history else 0.0


def central_reference_step(params, data: Dataset, rng, batch: int, program):
    """One mini-epoch of the virtual centralized model (divergence ref, eq. 17).

    Shared by the reference simulator and the batched engine so the two
    divergence baselines cannot drift apart.  ``program`` may be a
    ``ClientProgram`` or a bare ``CNNConfig`` (coerced).
    """
    program = as_program(program)
    n = len(data)
    steps = max(1, min(128, n // batch))
    idx = rng.permutation(n)[: steps * batch].reshape(steps, batch)
    xb = jnp.asarray(data.x[idx])
    yb = jnp.asarray(data.y[idx])
    params, _ = _local_epoch(params, xb, yb, program, steps, 1e-3)
    return params


@functools.partial(jax.jit, static_argnames=("program", "batch"))
def _eval_batches(params, x, y, program, batch: int):
    """``(n_batches,)`` ``program.metric`` over the consecutive row slices
    ``[i, i + batch)`` of the test set: the full batches through one
    ``lax.map``, the remainder (if any) as one more call."""
    n = x.shape[0]
    full = n // batch
    out = []
    if full:
        xs = x[: full * batch].reshape((full, batch) + x.shape[1:])
        ys = y[: full * batch].reshape((full, batch) + y.shape[1:])
        out.append(jax.lax.map(lambda xy: program.metric(params, *xy), (xs, ys)))
    if n % batch:
        out.append(program.metric(params, x[full * batch :], y[full * batch :])[None])
    return jnp.concatenate(out)


register_jit("eval_batches", _eval_batches)


class Evaluator:
    """Weighted mean of ``program.metric`` over a test set held on the
    device (classification accuracy for the CNN/MLP, next-token accuracy
    for the sequence programs).

    The test set is uploaded once, here: its bytes count as ``h2d_bytes``
    and one ``eval_test_uploads``.  Each call dispatches one compiled
    program over the batches of ``batch`` rows and reads their metrics back
    in one ``fetch`` span (``what="eval"``)."""

    def __init__(self, program, test: Dataset, batch: int = 512, telemetry=NULL_TELEMETRY):
        if not len(test):
            raise ValueError("the test set is empty")
        self.program = as_program(program)
        self.batch = int(batch)
        self.telemetry = telemetry
        self.x = telemetry.upload(test.x)
        self.y = telemetry.upload(test.y)
        telemetry.metrics.inc("eval_test_uploads")
        self.n = len(test)
        self._sizes = np.minimum(self.batch, self.n - np.arange(0, self.n, self.batch))

    def __call__(self, params) -> float:
        metrics = _eval_batches(params, self.x, self.y, self.program, self.batch)
        with self.telemetry.span("fetch", what="eval"):
            metrics = np.asarray(metrics, np.float64)
        return float(np.sum(metrics * self._sizes) / self.n)


def evaluate(
    params, program, test: Dataset, batch: int = 512, telemetry=NULL_TELEMETRY
) -> float:
    """One-off :class:`Evaluator` call (uploads ``test`` each time)."""
    return Evaluator(program, test, batch, telemetry)(params)


class HFLSimulation:
    """assignment: (M, N) binary matrix (possibly dual-connectivity rows)."""

    def __init__(
        self,
        clients: List[FLClient],
        assignment: np.ndarray,
        program,
        test: Dataset,
        schedule: HFLSchedule = HFLSchedule(1, 1),
        seed: int = 0,
        upp: float = 1.0,
        track_divergence: bool = False,
        central_batch: int = 50,
        cost_latency=None,
        compression: Optional[CompressionSpec] = None,
        faults=None,
        telemetry=None,
        cohort=None,
        server_momentum: float = 0.0,
        serve=None,
    ):
        self.clients = clients
        self.assignment = assignment
        self.program = as_program(program)
        self.test = test
        self.schedule = schedule
        self.rng = np.random.default_rng(seed)
        self.upp = upp
        # evaluation-under-traffic hook (repro.serving.traffic.ServeTraffic):
        # called with the post-reduce global model each cloud round; its
        # draws come from a keyed side-channel generator and it only READS
        # params, so serve=None runs are bit-identical to serve-on runs
        self.serve = serve
        # per-round cohort sampling (repro.federated.sampling.CohortSpec):
        # draws come from the spec's keyed side-channel generator, so the
        # engine RNG stream below is untouched — cohort=None stays
        # bit-identical to the pre-sampling trajectories
        self.cohort = cohort
        if cohort is not None and upp != 1.0:
            raise ValueError(
                "cohort sampling and UPP are both participation models; "
                "use upp=1.0 with a CohortSpec"
            )
        # optional cloud-side momentum on the aggregated model delta
        # (FedSGD server momentum; 0.0 = plain averaging, the pinned default)
        self.server_momentum = float(server_momentum)
        self._srv_vel = None
        self.tel = coerce_telemetry(telemetry) or NULL_TELEMETRY
        self._evaluator = Evaluator(self.program, test, telemetry=self.tel)
        self._round = 0
        # fault injection (repro.faults.FaultState); None = the historical
        # fault-free path, bit-identical to the golden trajectories
        self.faults = faults
        self._er = 0  # edge round within the current cloud round
        self._edge_got = None  # (N,) edges that received >= 1 upload this cloud round
        self.params = self.program.init(jax.random.PRNGKey(seed))
        self.track_divergence = track_divergence
        if track_divergence:
            self.central_params = jax.tree.map(lambda x: x, self.params)
            self.central_data = Dataset(
                np.concatenate([c.shard.x for c in clients], 0),
                np.concatenate([c.shard.y for c in clients], 0),
                self.program.n_classes,
            )
            self.central_batch = central_batch
        model_bits = tree_size_bytes(self.params) * 8
        self.accountant = CommAccountant(model_bits=model_bits)
        self.clock = WallClock(cost_latency) if cost_latency is not None else None
        # optional EU->edge uplink compression (composes with EARA: EARA cuts
        # rounds, compression cuts bits per round — paper Fig. 6 discussion)
        self.compression = compression
        self._uplink_bits = None
        self._comp_errors: Dict[int, object] = {}
        if compression is not None and compression.kind != "none":
            self._uplink_bits = compression.bits(self.params)
        else:
            # program-level uplink semantics (FedSGD gradient payloads;
            # model_bits for everything else, the accountant's default)
            self._uplink_bits = self.program.uplink_bits(model_bits)

    def _compress_upload(self, cid: int, start, trained):
        """Apply the spec to the EU's model delta with per-EU error feedback;
        with no spec, fall back to the program's own upload transform
        (FedSGD fp16 gradients; identity for everything else)."""
        if self.compression is None or self.compression.kind == "none":
            return self.program.quantize_upload(start, trained)
        delta = tree_sub(trained, start)
        sparse, err = self.compression.apply(delta, self._comp_errors.get(cid))
        self._comp_errors[cid] = err
        return tree_add(start, sparse)

    # -- one edge round: every client trains locally, edges aggregate --------
    def _edge_round(self, edge_params: List[dict]) -> List[float]:
        m, n = self.assignment.shape
        losses = []
        # sample participating clients: cohort draw (keyed side-channel
        # generator — the engine RNG is not consumed) or the UPP Bernoulli
        with self.tel.span("assignment", round=self._round, engine="reference"):
            if self.cohort is not None:
                participating = self.cohort.mask(
                    self._round, self._er, assignment=self.assignment
                )
            else:
                participating = self.rng.random(m) < self.upp
                if not participating.any():
                    participating[self.rng.integers(0, m)] = True
        failed = None
        if self.faults is not None:
            # churned-out / battery-dead EUs sit the round out; among the
            # rest, a mid-round loss mask marks EUs that train but whose
            # (single, no-retry) upload dies in the air.  Both masks come
            # from keyed fault streams — the engine RNG above is untouched.
            participating &= self.faults.participation(self._round)
            failed = (
                self.faults.failed_uploads(self._round, self._er)
                & participating
                & np.asarray(self.assignment).any(axis=1)
            )
            if self.tel.enabled:
                self.tel.metrics.inc("faults_dropped", int(failed.sum()))
        new_models: List[List[dict]] = [[] for _ in range(n)]
        new_sizes: List[List[float]] = [[] for _ in range(n)]
        with self.tel.span(
            "local_train", round=self._round, clients=int(participating.sum())
        ):
            for i, cl in enumerate(self.clients):
                edges = np.nonzero(self.assignment[i])[0]
                if len(edges) == 0 or not participating[i]:
                    continue
                # a DCA client starts from the average of its edges' models
                start = edge_params[edges[0]] if len(edges) == 1 else edge_aggregate(
                    [edge_params[j] for j in edges], [1.0] * len(edges)
                )
                upd, loss = cl.local_update(start, self.rng, epochs=self.schedule.local_steps)
                losses.append(loss)
                if failed is not None and failed[i]:
                    continue  # trained, transmitted, lost: masked out below
                upd = self._compress_upload(cl.cid, start, upd)
                for j in edges:
                    new_models[j].append(upd)
                    new_sizes[j].append(cl.data_size)
        with self.tel.span("edge_aggregate", round=self._round, edges=n):
            for j in range(n):
                if new_models[j]:
                    edge_params[j] = edge_aggregate(new_models[j], new_sizes[j])
                    if self._edge_got is not None:
                        self._edge_got[j] = True
        success = participating if failed is None else participating & ~failed
        self.accountant.on_edge_sync(
            self.assignment * success[:, None], uplink_bits=self._uplink_bits
        )
        if self.faults is not None:
            mc = self.accountant.dca_multicast_overhead
            for i in np.nonzero(failed)[0]:
                k = int(np.count_nonzero(self.assignment[i]))
                if k == 0:
                    continue
                self.accountant.on_wasted_upload(
                    int(i),
                    self._uplink_bits * (1.0 + (mc if k > 1 else 0.0)),
                    kind="dropped",
                )
            self.faults.debit_round(self._round, participating, self.assignment)
            self.faults.record_gauges(self.tel)
        if self.clock is not None:
            self.clock.on_edge_sync(self.assignment, participating)
        return losses

    def _central_step(self):
        self.central_params = central_reference_step(
            self.central_params, self.central_data, self.rng, self.central_batch,
            self.program,
        )

    def _maybe_repair(self, b: int) -> None:
        """Re-repair the assignment when channel drift invalidated memberships."""
        if not self.faults.spec.reassign:
            return
        new_lam, changed = self.faults.repair(b, self.assignment)
        if len(changed):
            self.assignment = new_lam
            if self.tel.enabled:
                self.tel.metrics.inc("faults_reassigned", int(len(changed)))

    def _cloud_update(self, old, agg):
        """Apply the cloud aggregate, optionally through server momentum.

        Delta form: ``v <- mu * v + (agg - old); new = old + v`` — with
        FedSGD single-step clients this is exactly centralized SGD+momentum
        on the aggregated gradient (velocity scaled by -lr), pinned by
        tests/test_stream.py against that oracle.  ``mu = 0`` reduces to
        plain averaging without touching the update path.
        """
        if not self.server_momentum:
            return agg
        delta = tree_sub(agg, old)
        if self._srv_vel is None:
            self._srv_vel = delta
        else:
            mu = self.server_momentum
            self._srv_vel = jax.tree.map(
                lambda v, d: mu * v + d, self._srv_vel, delta
            )
        return tree_add(old, self._srv_vel)

    def _edge_data_sizes(self) -> List[float]:
        return [
            sum(c.data_size for i, c in enumerate(self.clients) if self.assignment[i, j])
            for j in range(self.assignment.shape[1])
        ]

    def run(self, cloud_rounds: int, eval_every: int = 1) -> SimResult:
        n = self.assignment.shape[1]
        history: List[RoundMetrics] = []
        global_params = self.params
        edge_sizes = self._edge_data_sizes()
        comm = CommDelta(self.accountant) if self.tel.enabled else None
        wall_accum = sim_accum = 0.0
        for b in range(1, cloud_rounds + 1):
            t_round = time.perf_counter()
            sim0 = self.clock.seconds if self.clock is not None else 0.0
            self._round = b
            acc = None
            with self.tel.span("cloud_round", round=b, engine="reference"):
                if self.faults is not None:
                    self._maybe_repair(b)
                    if self.faults.spec.reassign:
                        edge_sizes = self._edge_data_sizes()
                    self._edge_got = np.zeros(n, bool)
                    if self.clock is not None:
                        # the straggler model reads the round's faded channel
                        self.clock.latency = self.faults.latency(b)
                edge_params = [global_params] * n
                losses: List[float] = []
                for k in range(self.schedule.edge_per_cloud):
                    self._er = k + 1
                    losses += self._edge_round(edge_params)
                with self.tel.span("cloud_reduce", round=b, edges=n):
                    if self.faults is not None:
                        # degraded-mode reduction: edges that received no
                        # upload all cloud round still hold the stale global
                        # model — skip their contribution (weight 0) rather
                        # than dilute the mean with it; if EVERY edge
                        # starved, the global model simply stands
                        w = [
                            s if self._edge_got[j] else 0.0
                            for j, s in enumerate(edge_sizes)
                        ]
                        if any(w):
                            global_params = self._cloud_update(
                                global_params, cloud_aggregate(edge_params, w)
                            )
                    else:
                        global_params = self._cloud_update(
                            global_params,
                            cloud_aggregate(edge_params, [max(s, 1) for s in edge_sizes]),
                        )
                self.accountant.on_cloud_sync(n)
                if self.clock is not None:
                    self.clock.on_cloud_sync()
                serve_rec = (
                    self.serve.on_round(b, lambda gp=global_params: gp)
                    if self.serve is not None
                    else None
                )
                div = 0.0
                if self.track_divergence:
                    for _ in range(self.schedule.cloud_period):
                        self._central_step()
                    div = weight_divergence(global_params, self.central_params)
                if b % eval_every == 0 or b == cloud_rounds:
                    with self.tel.span("eval", round=b) as sp:
                        acc = self._evaluator(global_params)
                        sp.set(acc=acc)
            round_wall = time.perf_counter() - t_round
            round_sim = (
                (self.clock.seconds - sim0) if self.clock is not None else 0.0
            )
            wall_accum += round_wall
            sim_accum += round_sim
            if acc is not None:
                history.append(
                    RoundMetrics(
                        b, acc, div, float(np.mean(losses)) if losses else 0.0,
                        wall_seconds=wall_accum, sim_seconds=sim_accum,
                    )
                )
                wall_accum = sim_accum = 0.0
            if self.tel.enabled:
                self.tel.metrics.set_gauge("eval_acc", acc) if acc is not None else None
                self.tel.on_round(
                    engine="reference", round=b, acc=acc,
                    loss=float(np.mean(losses)) if losses else 0.0,
                    wall_s=round_wall,
                    sim_s=round_sim if self.clock is not None else None,
                    **(serve_rec or {}),
                    **comm.take(),
                )
        self.params = global_params
        return SimResult(
            history, self.accountant, global_params,
            telemetry=self.tel if self.tel.enabled else None,
            serve_history=self.serve.history if self.serve is not None else None,
        )


def hetero_final_params(programs, trees) -> Dict[str, dict]:
    """Label one final parameter tree per architecture group.

    Keys are the program names; two groups that share a name (same
    architecture, different frozen config) get a positional suffix so no
    tree is silently dropped.
    """
    out: Dict[str, dict] = {}
    for g, (prog, tree) in enumerate(zip(programs, trees)):
        key = prog.name if prog.name not in out else f"{prog.name}#{g}"
        out[key] = tree
    return out


class HeteroHFLSimulation:
    """Readable reference for heterogeneous-MODEL hierarchical FL.

    Clients may carry different ``ClientProgram``s; the population splits
    into architecture groups (``federated.programs.group_clients``) and the
    paper's two-level schedule runs once per group — per-edge FedAvg within
    each architecture, per-group cloud reduction — with one extra stage the
    homogeneous pipeline does not have: once per cloud round, after the
    edge rounds and before the cloud reduction, each edge fuses its G
    per-group models by ensemble logit distillation on its own public
    shard (``engine.distill.distill_edge``).

    This class is the parity oracle for the engines' group-aware paths: it
    consumes the numpy RNG stream in exactly the order the engines do
    (participation draw, then per-client batch draws in global client
    order, then per-edge public-batch draws in edge order), trains every
    client through the same ``FLClient.local_update``, and charges the
    accountant with the same per-group calls.

    ``public`` is one ``Dataset`` per edge (the KD fuse's shared data);
    ``distill=None`` disables the fuse (groups then evolve independently —
    still a valid hetero federation, just without knowledge transfer).
    """

    def __init__(
        self,
        clients: List[FLClient],
        assignment: np.ndarray,
        test: Dataset,
        schedule: HFLSchedule = HFLSchedule(1, 1),
        seed: int = 0,
        upp: float = 1.0,
        public: "Optional[List[Dataset]]" = None,
        distill=None,
        compression: Optional[CompressionSpec] = None,
        telemetry=None,
    ):
        # lazy: no engine dependency at module import time
        from repro.engine.distill import check_distillable, check_public_shards

        self.clients = clients
        self.assignment = np.asarray(assignment)
        self.test = test
        self.schedule = schedule
        self.rng = np.random.default_rng(seed)
        self.upp = upp
        self.tel = coerce_telemetry(telemetry) or NULL_TELEMETRY
        self._round = 0
        self.programs, self.group_of = group_clients(clients)
        self._evaluators = [Evaluator(p, test, telemetry=self.tel) for p in self.programs]
        self.group_params = [
            p.init(jax.random.PRNGKey(seed)) for p in self.programs
        ]
        self._group_bits = [tree_size_bytes(t) * 8 for t in self.group_params]
        self.distill = distill if len(self.programs) > 1 else None
        self.public = public
        if self.distill is not None:
            check_public_shards(public, self.assignment.shape[1])
            check_distillable(self.programs)
        self.accountant = CommAccountant(model_bits=self._group_bits[0])
        self.compression = compression
        self._comp_errors: Dict[int, object] = {}
        if compression is not None and compression.kind != "none":
            self._uplink_bits = [compression.bits(t) for t in self.group_params]
        else:
            self._uplink_bits = [
                p.uplink_bits(b) for p, b in zip(self.programs, self._group_bits)
            ]

    def _compress_upload(self, cid: int, start, trained):
        if self.compression is None or self.compression.kind == "none":
            return self.clients[cid].program.quantize_upload(start, trained)
        delta = tree_sub(trained, start)
        sparse, err = self.compression.apply(delta, self._comp_errors.get(cid))
        self._comp_errors[cid] = err
        return tree_add(start, sparse)

    def _edge_round(self, edge_params: List[List[dict]]) -> List[float]:
        """One edge round; ``edge_params[g][j]`` is edge j's group-g model."""
        m, n = self.assignment.shape
        losses = []
        with self.tel.span("assignment", round=self._round, engine="reference-hetero"):
            participating = self.rng.random(m) < self.upp
            if not participating.any():
                participating[self.rng.integers(0, m)] = True
        new_models: Dict[tuple, List[dict]] = {}
        new_sizes: Dict[tuple, List[float]] = {}
        with self.tel.span(
            "local_train", round=self._round, clients=int(participating.sum())
        ):
            for i, cl in enumerate(self.clients):
                edges = np.nonzero(self.assignment[i])[0]
                if len(edges) == 0 or not participating[i]:
                    continue
                g = int(self.group_of[i])
                rows = edge_params[g]
                start = rows[edges[0]] if len(edges) == 1 else edge_aggregate(
                    [rows[j] for j in edges], [1.0] * len(edges)
                )
                upd, loss = cl.local_update(start, self.rng, epochs=self.schedule.local_steps)
                losses.append(loss)
                upd = self._compress_upload(cl.cid, start, upd)
                for j in edges:
                    new_models.setdefault((g, j), []).append(upd)
                    new_sizes.setdefault((g, j), []).append(cl.data_size)
        with self.tel.span("edge_aggregate", round=self._round, edges=n):
            for (g, j), models in new_models.items():
                edge_params[g][j] = edge_aggregate(models, new_sizes[(g, j)])
        for g in range(len(self.programs)):
            mask = (self.group_of == g) & participating
            self.accountant.on_edge_sync(
                self.assignment * mask[:, None],
                uplink_bits=self._uplink_bits[g],
                downlink_bits=None if len(self.programs) == 1 else self._group_bits[g],
                count_round=(g == 0),
            )
        return losses

    def _kd_fuse(self, edge_params: List[List[dict]]) -> List[List[dict]]:
        from repro.engine.distill import distill_edge, draw_public_batches

        n = self.assignment.shape[1]
        with self.tel.span(
            "kd_fuse", round=self._round, edges=n, groups=len(self.programs)
        ):
            idx = draw_public_batches(
                self.rng, [len(s) for s in self.public], self.distill
            )
            for j in range(n):
                xb = self.public[j].x[idx[j]]  # (steps, B, *feat)
                fused, kd_losses = distill_edge(
                    self.programs, [edge_params[g][j] for g in range(len(self.programs))],
                    xb, self.distill,
                )
                if self.tel.enabled:
                    for loss in kd_losses:
                        self.tel.metrics.observe("kd_loss", loss)
                for g, tree in enumerate(fused):
                    edge_params[g][j] = tree
        return edge_params

    def run(self, cloud_rounds: int, eval_every: int = 1) -> SimResult:
        n = self.assignment.shape[1]
        n_groups = len(self.programs)
        history: List[RoundMetrics] = []
        group_params = self.group_params
        edge_sizes = group_edge_sizes(self.clients, self.assignment, self.group_of)
        cloud_bits = None if n_groups == 1 else float(sum(self._group_bits))
        comm = CommDelta(self.accountant) if self.tel.enabled else None
        wall_accum = 0.0
        for b in range(1, cloud_rounds + 1):
            t_round = time.perf_counter()
            self._round = b
            acc = None
            with self.tel.span("cloud_round", round=b, engine="reference-hetero"):
                edge_params = [[tree] * n for tree in group_params]
                losses: List[float] = []
                for _ in range(self.schedule.edge_per_cloud):
                    losses += self._edge_round(edge_params)
                if self.distill is not None:
                    edge_params = self._kd_fuse(edge_params)
                with self.tel.span("cloud_reduce", round=b, groups=n_groups):
                    group_params = [
                        cloud_aggregate(edge_params[g], edge_sizes[g])
                        for g in range(n_groups)
                    ]
                self.accountant.on_cloud_sync(n, bits=cloud_bits)
                if b % eval_every == 0 or b == cloud_rounds:
                    with self.tel.span("eval", round=b) as sp:
                        acc = float(
                            np.mean(
                                [
                                    self._evaluators[g](group_params[g])
                                    for g in range(n_groups)
                                ]
                            )
                        )
                        sp.set(acc=acc)
            round_wall = time.perf_counter() - t_round
            wall_accum += round_wall
            if acc is not None:
                history.append(
                    RoundMetrics(
                        b, acc, 0.0, float(np.mean(losses)) if losses else 0.0,
                        wall_seconds=wall_accum,
                    )
                )
                wall_accum = 0.0
            if self.tel.enabled:
                self.tel.metrics.set_gauge("eval_acc", acc) if acc is not None else None
                self.tel.on_round(
                    engine="reference-hetero", round=b, acc=acc,
                    loss=float(np.mean(losses)) if losses else 0.0,
                    wall_s=round_wall, sim_s=None, **comm.take(),
                )
        self.group_params = group_params
        final = (
            group_params[0]
            if n_groups == 1
            else hetero_final_params(self.programs, group_params)
        )
        return SimResult(
            history, self.accountant, final,
            telemetry=self.tel if self.tel.enabled else None,
        )


def centralized_baseline(
    clients: List[FLClient],
    program,
    test: Dataset,
    rounds: int,
    batch: int = 50,
    seed: int = 0,
    eval_every: int = 1,
) -> List[RoundMetrics]:
    """The paper's benchmark: all data pooled at one server (batch 50/30)."""
    program = as_program(program)
    rng = np.random.default_rng(seed)
    data = Dataset(
        np.concatenate([c.shard.x for c in clients], 0),
        np.concatenate([c.shard.y for c in clients], 0),
        program.n_classes,
    )
    params = program.init(jax.random.PRNGKey(seed))
    evaluator = Evaluator(program, test)
    history = []
    n = len(data)
    wall_accum = 0.0
    for r in range(1, rounds + 1):
        t_round = time.perf_counter()
        steps = max(1, min(128, n // batch))
        idx = rng.permutation(n)[: steps * batch].reshape(steps, batch)
        xb, yb = jnp.asarray(data.x[idx]), jnp.asarray(data.y[idx])
        params, loss = _local_epoch(params, xb, yb, program, steps, 1e-3)
        if r % eval_every == 0 or r == rounds:
            acc = evaluator(params)
            wall_accum += time.perf_counter() - t_round
            history.append(
                RoundMetrics(r, acc, 0.0, float(loss), wall_seconds=wall_accum)
            )
            wall_accum = 0.0
        else:
            wall_accum += time.perf_counter() - t_round
    return history

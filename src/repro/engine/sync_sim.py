"""Batched synchronous HFL engine.

Same semantics as ``federated.simulation.HFLSimulation`` — the same RNG
stream, participation sampling, DCA starts, schedule, and accounting — but
the hot loop is restructured for scale.  The engine is model-agnostic: it
trains whatever ``ClientProgram`` (``federated.programs``) the clients
carry — the paper's CNN, the MLP, or the transformer-LM — through the same
flat-buffer pipelines:

  * local training: one jitted cohort call per same-shape client group
    (``engine.cohort``) instead of one jitted call per client;
  * model state is *flat-major*: clients exchange (D,) rows, edge models
    live in one (E, D) device matrix, and FedAvg runs on (N, D) matrices
    through the Pallas kernels (``backend="pallas"``) or plain-XLA
    contractions (``backend="reference"``);
  * uploads optionally pass through a ``CompressionSpec`` applied to the
    flat model delta (global top-k over all parameters, vs the reference
    simulator's per-leaf top-k) with per-client error feedback, and the
    accountant then counts compressed bits.

Two pipelines (``pipeline=``):

  * ``"device"`` (default) — the round executes as a handful of
    fixed-shape device programs: client shards live in a
    ``DeviceShardStore`` (batches gathered on device from int32 indices),
    every edge's aggregation is ONE ``flat_segment_mean`` call over the
    (P, D) membership-pair matrix (segments = edges; per-round
    participation travels in the weights so shapes never change), DCA
    start averaging is one segment call with segments = clients, and the
    cloud mean reduces the (E, D) edge matrix directly.  O(1) device
    dispatches per round instead of O(E), no per-edge-size recompiles.
  * ``"host"`` — the PR 1 host-major loop (per-edge ``flat_mean`` calls,
    numpy batch stacking), kept as the comparison baseline for
    ``benchmarks/engine_bench.py`` and the equivalence tests.

Heterogeneous-model federation (ISSUE 5): clients under one edge may carry
DIFFERENT programs.  Every structure above becomes per-ARCHITECTURE-group:
one (E, D_g) edge matrix, one cohort-plan partition, one membership-pair
segment aggregation, and one cloud reduction per distinct program, with the
groups fused once per cloud round by logit distillation on a device-resident
public shard (``engine.distill``, ``distill=DistillSpec(...)`` +
``public_shards=[...]``).  A homogeneous population is the single-group
corner of the same code path — same ops, same RNG stream — so those runs
stay bit-identical to the pre-distillation engine (pinned by the golden
trajectories in ``tests/test_consistency.py``).

The engine consumes the numpy RNG stream draw-for-draw like the reference
simulator, so a fixed seed reproduces the reference accuracy trajectory
exactly (pinned to 1e-6 by ``tests/test_engine.py``); parameters track to
~1e-3 (the batched conv backward accumulates in a different order, which
Adam amplifies — predictions are unaffected).
"""
from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressionSpec
from repro.core.hfl import CommAccountant, HFLSchedule, WallClock, weight_divergence
from repro.data.synthetic_health import Dataset
from repro.engine.cohort import (
    CohortPlan,
    _cohort_epoch_flat,
    build_group_state,
    make_job,
    run_cohorts,
)
from repro.engine.distill import (
    DistillSpec,
    check_distillable,
    check_public_shards,
    distill_fuse_flat,
    draw_public_batches,
)
from repro.engine.flatten import (
    BACKENDS,
    FlatPack,
    compress_flat_upload,
    flat_mean,
    flat_segment_mean,
)
from repro.engine.store import DeviceShardStore
from repro.federated.client import FLClient
from repro.federated.programs import as_program, group_edge_sizes
from repro.federated.simulation import (
    Evaluator,
    RoundMetrics,
    SimResult,
    central_reference_step,
    hetero_final_params,
)
from repro.telemetry import NULL_TELEMETRY, coerce_telemetry, register_jit
from repro.telemetry.report import CommDelta
from repro.utils.tree import tree_size_bytes

PIPELINES = ("device", "host")


@partial(jax.jit, static_argnames=("n_segments", "backend"))
def _segment_agg_keep(upd, seg_ids, weights, has, prev, n_segments: int, backend: str):
    """Fused per-edge FedAvg + keep-previous-model-for-empty-edges: one
    dispatch instead of a segment call, a mask upload, and a select."""
    agg = flat_segment_mean(upd, seg_ids, weights, n_segments, backend=backend)
    return jnp.where(has[:, None], agg, prev)


register_jit("segment_agg_keep", _segment_agg_keep)


class BatchedSyncEngine:
    """Drop-in replacement for ``HFLSimulation`` with cohort batching.

    Knobs (constructor):

    * ``program`` — any ``ClientProgram`` (``federated.PROGRAMS``: "cnn",
      "mlp", "lm", "moe", "mamba", "rwkv", or a "fedsgd" wrapper); a bare
      ``CNNConfig`` is coerced for legacy call sites.  The program picks
      the local optimizer and (FedSGD) the uplink payload.  Clients may
      carry programs that DIFFER from it (and from each other): the engine
      partitions the population into architecture groups and runs every
      pipeline stage per group.
    * ``pipeline`` — ``"device"`` (default: shard store + fused segment
      aggregation, O(1) dispatches per round) | ``"host"`` (the PR 1
      host-major loop, kept as benchmark baseline).
    * ``backend`` — flat-buffer aggregation path: ``"pallas"`` (kernels;
      tiny-N and off-TPU calls route to jitted contractions) |
      ``"reference"`` (plain-XLA contractions).
    * ``compression`` — ``None`` | ``CompressionSpec(kind="topk" |
      "ternary" | "none", ...)``; applied to the flat update delta with
      per-client error feedback, and the accountant then counts
      ``compression.bits``.  Takes precedence over the program's own
      uplink quantization.
    * ``upp`` — per-round client participation probability in (0, 1].
    * ``public_shards`` / ``distill`` — the distillation aggregation layer
      for heterogeneous-model populations: one public ``Dataset`` per edge
      and a ``DistillSpec``; once per cloud round (between the edge rounds
      and the cloud reduction) each edge's per-group models are fused by
      ensemble logit distillation on its public shard.  Ignored for
      homogeneous populations (the fuse would be self-distillation).

    Clients may carry heterogeneous hyperparameters (``lr``,
    ``batch_size``, ``local_epochs``, ``max_steps``): the cohort plan
    groups same-tuple clients so shapes stay fixed per group.
    """

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
        backend: str = "pallas",
        compression: Optional[CompressionSpec] = None,
        pipeline: str = "device",
        public_shards: Optional[Sequence[Dataset]] = None,
        distill: Optional[DistillSpec] = None,
        faults=None,
        telemetry=None,
        cohort=None,
        server_momentum: float = 0.0,
        serve=None,
    ):
        if pipeline not in PIPELINES:
            raise ValueError(f"pipeline must be one of {PIPELINES}, got {pipeline!r}")
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        self.tel = coerce_telemetry(telemetry) or NULL_TELEMETRY
        self._round = 0
        self.clients = clients
        self.assignment = assignment
        self.program = as_program(program)  # bare CNNConfig still accepted
        self.test = test
        self.schedule = schedule
        self.rng = np.random.default_rng(seed)
        self.upp = upp
        # per-round cohort sampling: keyed side-channel draws (the engine
        # RNG stream stays untouched — cohort=None is bit-identical to the
        # pre-sampling trajectories)
        self.cohort = cohort
        if cohort is not None and upp != 1.0:
            raise ValueError(
                "cohort sampling and UPP are both participation models; "
                "use upp=1.0 with a CohortSpec"
            )
        # cloud-side momentum on the aggregated delta (0.0 = plain FedAvg)
        self.server_momentum = float(server_momentum)
        self._srv_vel = None
        self.params = self.program.init(jax.random.PRNGKey(seed))
        self.backend = backend
        self.compression = compression
        self.pipeline = pipeline
        self.pack = FlatPack(self.params)
        # architecture groups: one of everything below per distinct program
        gs = build_group_state(
            clients, self.program, self.params, self.pack, seed, compression
        )
        self.groups, self.group_of = gs.programs, gs.group_of
        # one evaluator per group: the test set stays on the device across runs
        self._evaluators = [Evaluator(p, test, telemetry=self.tel) for p in self.groups]
        self.group_params, self.packs = gs.params, gs.packs
        self._group_bits, self._uplink_bits = gs.bits, gs.uplink_bits
        n_groups = len(self.groups)
        # evaluation-under-traffic hook (serving.traffic.ServeTraffic): reads
        # the post-reduce global tree via the group FlatPack; side-channel
        # draws keep serve=None trajectories bit-identical to serve-on runs
        self.serve = serve
        if serve is not None and n_groups > 1:
            raise ValueError(
                "serve traffic targets THE global model; heterogeneous-model "
                "populations have one per architecture group"
            )
        self.distill = distill if n_groups > 1 else None
        self.public_store = None
        if self.distill is not None:
            check_public_shards(public_shards, assignment.shape[1])
            check_distillable(self.groups)
            self.public_store = DeviceShardStore.from_shards(public_shards)
        self.track_divergence = track_divergence
        if track_divergence:
            if n_groups > 1:
                raise ValueError(
                    "track_divergence is defined against ONE virtual central "
                    "model; heterogeneous-model populations have no such "
                    "reference"
                )
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
        # fault injection (repro.faults.FaultState); None = the historical
        # fault-free path, bit-identical to the golden trajectories
        self.faults = faults
        self._er = 0  # edge round within the current cloud round
        self._edge_got = None  # per-group (N,) edges that aggregated this cloud round
        self._errors: Dict[int, object] = {}
        self._data_sizes = np.array([c.data_size for c in clients], np.float32)
        self._build_pair_structure(assignment)
        self.store = DeviceShardStore(clients) if pipeline == "device" else None
        self._plan = CohortPlan(clients, self.program) if pipeline == "device" else None
        if self.tel.enabled:
            for g, prog in enumerate(self.groups):
                self.tel.metrics.set_gauge(
                    f"group_clients/{prog.name}", int((self.group_of == g).sum())
                )

    def _build_pair_structure(self, assignment) -> None:
        """(Re)build the round structure from an assignment matrix: the
        (client, edge) membership pairs in client-major order, their
        per-architecture-group restrictions, and the SCA fast-path indices.
        Called once at construction and again whenever fault-driven
        re-repair (``FaultSpec.reassign``) rewrites the assignment;
        participation varies per round but travels in the segment WEIGHTS,
        so every device program keeps a fixed shape between rebuilds."""
        asn = np.asarray(assignment)
        self.assignment = asn
        pc, pe = np.nonzero(asn)
        self._pair_clients = pc.astype(np.int64)
        self._pair_edges = pe.astype(np.int64)
        self._pair_clients_dev = jnp.asarray(pc, jnp.int32)
        self._pair_edges_dev = jnp.asarray(pe, jnp.int32)
        self._pair_ones = jnp.ones((len(pc),), jnp.float32)
        # the same pair structure restricted to each architecture group (the
        # per-group FedAvg segment call must only see its own clients' rows)
        self._gpairs = []
        for g in range(len(self.groups)):
            gm = self.group_of[pc] == g
            self._gpairs.append(
                (
                    pc[gm].astype(np.int64),
                    pe[gm].astype(np.int64),
                    jnp.asarray(pe[gm], jnp.int32),
                )
            )
        self._has_edge = asn.any(axis=1)
        # SCA fast path: with single-connectivity every DCA start IS an edge
        # row, so starts reduce to one gather instead of a segment mean
        self._single_edge = bool((asn.sum(axis=1) <= 1).all())
        self._client_edge = np.where(self._has_edge, asn.argmax(axis=1), 0).astype(
            np.int64
        )

    def _maybe_repair(self, b: int) -> None:
        """Re-repair the assignment when channel drift invalidated memberships."""
        if not self.faults.spec.reassign:
            return
        new_lam, changed = self.faults.repair(b, self.assignment)
        if len(changed):
            self._build_pair_structure(new_lam)
            if self.tel.enabled:
                self.tel.metrics.inc("faults_reassigned", int(len(changed)))

    def _mean(self, rows: List[jnp.ndarray], weights) -> jnp.ndarray:
        return flat_mean(
            jnp.stack(rows), np.asarray(weights, np.float32), backend=self.backend
        )

    def _edge_account(self, participating: np.ndarray, failed=None) -> None:
        """Charge one edge round: per architecture group, each group's
        clients pay that group's uplink/downlink payload (one masked
        ``on_edge_sync`` per group; the round itself counts once).  A
        ``failed`` mask (fault-injected runs) removes mid-round-lost
        uploads from the useful totals and charges them as wasted bits;
        the straggler clock and the energy debit still see every ATTEMPTED
        client — a lost upload was transmitted and waited for."""
        success = participating if failed is None else participating & ~failed
        for g in range(len(self.groups)):
            mask = (self.group_of == g) & success
            self.accountant.on_edge_sync(
                self.assignment * mask[:, None],
                uplink_bits=self._uplink_bits[g],
                downlink_bits=None if len(self.groups) == 1 else self._group_bits[g],
                count_round=(g == 0),
            )
        if failed is not None:
            mc = self.accountant.dca_multicast_overhead
            for i in np.nonzero(failed)[0]:
                k = int(np.count_nonzero(self.assignment[i]))
                if k == 0:
                    continue
                self.accountant.on_wasted_upload(
                    int(i),
                    self._uplink_bits[self.group_of[i]]
                    * (1.0 + (mc if k > 1 else 0.0)),
                    kind="dropped",
                )
        if self.faults is not None:
            self.faults.debit_round(self._round, participating, self.assignment)
            self.faults.record_gauges(self.tel)
        if self.clock is not None:
            self.clock.on_edge_sync(self.assignment, participating)

    def _draw_participation(self, m: int) -> np.ndarray:
        """This round's (M,) participation mask.  Cohort sampling reads the
        keyed side channel (engine RNG untouched); the UPP path consumes the
        engine RNG draw-for-draw like the reference simulator.  Shared by
        every sync pipeline (host / device / mesh) so they stay on one RNG
        stream."""
        if self.cohort is not None:
            return self.cohort.mask(self._round, self._er, assignment=self.assignment)
        participating = self.rng.random(m) < self.upp
        if not participating.any():
            participating[self.rng.integers(0, m)] = True
        return participating

    def _broadcast_rows(self, global_rows: List[jnp.ndarray], n: int) -> List[jnp.ndarray]:
        """Per-group (E, D) edge matrices seeded from the global rows at the
        top of a cloud round (the mesh engine overrides this to lay the
        matrix out over the device mesh)."""
        return [jnp.broadcast_to(row, (n, row.shape[0])) for row in global_rows]

    def _cloud_mean(self, edge_mat: jnp.ndarray, weights) -> jnp.ndarray:
        """Cloud FedAvg of one group's (E, D) edge matrix (paper eq. 9).
        Host ``weights`` are uploaded through ``tel.upload``.  Traceable
        (``tel.jit_cost`` lowers it); the mesh engine overrides this with
        the two-stage partial-sum + ``psum`` reduction — the only
        cross-edge collective on the mesh."""
        if isinstance(weights, np.ndarray):
            weights = self.tel.upload(weights)
        return flat_mean(edge_mat, weights, backend=self.backend)

    # -- one edge round, device pipeline --------------------------------------
    def _client_starts(self, edge_mat: jnp.ndarray) -> jnp.ndarray:
        """(M, D) per-client DCA start rows from the (E, D) edge matrix.

        A DCA client starts from the unweighted mean of its edges' models —
        one segment call with segments = clients over the membership pairs.
        No RNG is consumed, so computing starts for every client
        (participating or not) keeps the shape static at no parity cost —
        unused rows are never read (including other groups' rows when
        ``edge_mat`` belongs to one architecture group).
        """
        return flat_segment_mean(
            edge_mat[self._pair_edges_dev],
            self._pair_clients_dev,
            self._pair_ones,
            self.assignment.shape[0],
            backend=self.backend,
        )

    def _edge_round_device(self, edge_mats: List[jnp.ndarray]):
        """One edge round as fixed-shape device programs; returns the new
        per-group (E, D_g) edge matrices and the per-client losses."""
        tel = self.tel
        m, n = self.assignment.shape
        with tel.span("assignment", round=self._round, engine="sync-device"):
            participating = self._draw_participation(m)
            failed = None
            if self.faults is not None:
                # churned-out / battery-dead EUs sit the round out; mid-round
                # losses train but are masked from aggregation.  Keyed fault
                # streams only — the engine RNG above is untouched.
                participating &= self.faults.participation(self._round)
                failed = (
                    self.faults.failed_uploads(self._round, self._er)
                    & participating
                    & self._has_edge
                )
                if tel.enabled:
                    tel.metrics.inc("faults_dropped", int(failed.sum()))
            active = self._has_edge & participating
            # the plan's draw consumes the RNG in client order, mirroring the
            # reference; grouping itself was precomputed at construction
            groups, passthrough = self._plan.draw(
                self.rng, active, self.schedule.local_steps
            )
            if tel.enabled:
                tel.metrics.set_gauge("participating", int(active.sum()))
                for g in groups:
                    tel.metrics.observe("cohort_size", len(g.members))
                    need = float(g.steps * g.batch)
                    occ = np.minimum(self._plan.sizes[g.members], need) / need
                    tel.metrics.observe(
                        "cohort_padding_waste", float(1.0 - occ.mean())
                    )
        # lazy DCA start rows: the SCA corner (every client on one edge) is a
        # plain gather per cohort; only dual-connectivity pays the segment
        # call for the full (M, D) matrix
        starts_full: Dict[int, jnp.ndarray] = {}
        group_idx = {p: g for g, p in enumerate(self.groups)}

        def starts_for(ids: np.ndarray, g: int) -> jnp.ndarray:
            if self._single_edge:
                return jnp.take(
                    edge_mats[g], tel.upload(self._client_edge[ids], np.int32), axis=0
                )
            if g not in starts_full:
                starts_full[g] = self._client_starts(edge_mats[g])
            return jnp.take(starts_full[g], tel.upload(ids, np.int32), axis=0)
        # train each cohort flat-major: starts gather -> per-epoch on-device
        # batch gather -> fused (C, D)-in/(C, D)-out epoch.  Losses stay on
        # device until metrics time so the aggregation dispatches below can
        # queue behind the (async-dispatched) epochs without a host sync.
        # Cohorts and rows are kept per ARCHITECTURE group throughout.
        mats: List[List[jnp.ndarray]] = [[] for _ in self.groups]
        loss_chunks = []
        row_of = np.zeros(m, np.int64)
        offsets = [0] * len(self.groups)
        for g in groups:
            gi = group_idx[g.program]
            with tel.span(
                "cohort_epoch", round=self._round, program=g.program.name,
                clients=len(g.members), epochs=int(g.idx.shape[1]),
                steps=g.steps, batch=g.batch,
            ) as sp:
                flat = starts_for(g.members, gi)
                for e in range(g.idx.shape[1]):
                    xb, yb = self.store.gather(
                        tel.upload(g.members, np.int32), tel.upload(g.idx[:, e], np.int32)
                    )
                    if e == 0:
                        cost = tel.jit_cost(
                            "cohort_epoch_flat", _cohort_epoch_flat,
                            flat, xb, yb, self.packs[gi].spec, g.program,
                            g.steps, g.lr,
                        )
                        if cost:
                            sp.set(**cost)
                    flat, loss = _cohort_epoch_flat(
                        flat, xb, yb, self.packs[gi].spec, g.program, g.steps, g.lr
                    )
            mats[gi].append(flat)
            loss_chunks.append(loss)
            row_of[g.members] = np.arange(offsets[gi], offsets[gi] + len(g.members))
            offsets[gi] += len(g.members)
        if len(passthrough):  # empty shards upload their start row untouched
            for gi in range(len(self.groups)):
                pt = passthrough[self.group_of[passthrough] == gi]
                if not len(pt):
                    continue
                mats[gi].append(starts_for(pt, gi))
                loss_chunks.append(np.zeros(len(pt), np.float32))
                row_of[pt] = np.arange(offsets[gi], offsets[gi] + len(pt))
                offsets[gi] += len(pt)
        compressing = self.compression is not None and self.compression.kind != "none"
        for gi, prog in enumerate(self.groups):
            job_cids = np.nonzero(active & (self.group_of == gi))[0]
            if not len(job_cids):
                continue  # no member of this architecture trained this round
            upd_matrix = (
                jnp.concatenate(mats[gi], axis=0) if len(mats[gi]) > 1 else mats[gi][0]
            )
            quantizing = not compressing and prog.quantizes_upload
            if compressing or quantizing:
                start_rows = starts_for(job_cids, gi)
                trained_rows = jnp.take(
                    upd_matrix, tel.upload(row_of[job_cids], np.int32), axis=0
                )
                if quantizing:
                    # program-level upload transform (FedSGD fp16 gradients):
                    # one batched op over the (C, D) matrices, no per-row state
                    upd_matrix = prog.quantize_upload(start_rows, trained_rows)
                    row_of[job_cids] = np.arange(len(job_cids))
                else:
                    rows = []
                    for k, i in enumerate(job_cids):
                        if failed is not None and failed[i]:
                            # lost upload: weight-0 row below, and no
                            # error-feedback update (mirrors the reference,
                            # which never compresses a lost upload)
                            rows.append(trained_rows[k])
                        else:
                            rows.append(
                                compress_flat_upload(
                                    self.compression, self._errors, int(i),
                                    start_rows[k], trained_rows[k],
                                )
                            )
                        row_of[i] = k
                    upd_matrix = jnp.stack(rows)
            # every edge's FedAvg in ONE segment call over the group's pairs
            with tel.span(
                "edge_aggregate", round=self._round, group=prog.name,
                clients=len(job_cids), edges=n,
            ) as sp:
                pc_g, pe_g, pe_g_dev = self._gpairs[gi]
                agg_mask = (
                    participating if failed is None else participating & ~failed
                )
                part_pairs = agg_mask[pc_g]
                take = row_of[pc_g]
                if len(take) == upd_matrix.shape[0] and np.array_equal(
                    take, np.arange(len(take))
                ):
                    upd = upd_matrix  # rows already in pair order: skip the gather
                else:
                    upd = jnp.take(upd_matrix, tel.upload(take, np.int32), axis=0)
                # edges with no participants of this group keep their previous
                # group model
                has = np.bincount(pe_g, weights=part_pairs, minlength=n) > 0
                w_dev = tel.upload(self._data_sizes[pc_g] * part_pairs)
                has_dev = tel.upload(has)
                cost = tel.jit_cost(
                    "segment_agg_keep", _segment_agg_keep,
                    upd, pe_g_dev, w_dev, has_dev, edge_mats[gi], n, self.backend,
                )
                if cost:
                    sp.set(**cost)
                edge_mats[gi] = _segment_agg_keep(
                    upd, pe_g_dev, w_dev, has_dev, edge_mats[gi], n, self.backend
                )
                if self._edge_got is not None:
                    self._edge_got[gi] |= has
        self._edge_account(participating, failed)
        return edge_mats, loss_chunks

    # -- one edge round, host pipeline --------------------------------------
    def _edge_round(self, edge_rows: List[List[jnp.ndarray]]) -> List[float]:
        """The PR 1 host-major round, preserved (host batch stacking,
        per-edge ``flat_mean`` loop, XLA-conv cohort step) as the benchmark
        baseline and equivalence-test counterpart.  ``edge_rows[g][j]`` is
        edge j's model for architecture group g."""
        m, n = self.assignment.shape
        with self.tel.span("assignment", round=self._round, engine="sync-host"):
            participating = self._draw_participation(m)
            failed = None
            if self.faults is not None:
                participating &= self.faults.participation(self._round)
                failed = (
                    self.faults.failed_uploads(self._round, self._er)
                    & participating
                    & self._has_edge
                )
                if self.tel.enabled:
                    self.tel.metrics.inc("faults_dropped", int(failed.sum()))
            # job prep consumes the RNG in client order, mirroring the reference
            jobs, job_edges = [], []
            for i, cl in enumerate(self.clients):
                edges = np.nonzero(self.assignment[i])[0]
                if len(edges) == 0 or not participating[i]:
                    continue
                rows = edge_rows[self.group_of[i]]
                # a DCA client starts from the average of its edges' models
                start = rows[edges[0]] if len(edges) == 1 else self._mean(
                    [rows[j] for j in edges], [1.0] * len(edges)
                )
                jobs.append(make_job(cl, start, self.rng, epochs=self.schedule.local_steps))
                job_edges.append(edges)
        trained = run_cohorts(
            jobs, self.program, self.pack, impl="xla", telemetry=self.tel
        )
        compressing = self.compression is not None and self.compression.kind != "none"
        losses = []
        new_cids: Dict[tuple, List[int]] = {}
        new_rows: Dict[tuple, List[jnp.ndarray]] = {}
        new_sizes: Dict[tuple, List[float]] = {}
        for job, edges in zip(jobs, job_edges):
            cid = job.client.cid
            gi = self.group_of[cid]
            losses.append(trained.loss[cid])
            if failed is not None and failed[cid]:
                continue  # trained, transmitted, lost: masked out of FedAvg
            quantizing = not compressing and job.client.program.quantizes_upload
            transforming = compressing or quantizing
            if compressing:
                row = compress_flat_upload(
                    self.compression, self._errors, cid, job.start_flat, trained.row(cid)
                )
            elif quantizing:
                row = job.client.program.quantize_upload(job.start_flat, trained.row(cid))
            for j in edges:
                new_cids.setdefault((j, gi), []).append(cid)
                if transforming:
                    new_rows.setdefault((j, gi), []).append(row)
                new_sizes.setdefault((j, gi), []).append(job.client.data_size)
        with self.tel.span(
            "edge_aggregate", round=self._round, engine="sync-host",
            edges=len(new_cids),
        ):
            for (j, gi), cids in new_cids.items():
                # untransformed fast path: one gather from the cohort matrix
                mat = (
                    jnp.stack(new_rows[(j, gi)])
                    if (j, gi) in new_rows
                    else trained.gather(cids)
                )
                edge_rows[gi][j] = flat_mean(
                    mat, np.asarray(new_sizes[(j, gi)], np.float32), backend=self.backend
                )
                if self._edge_got is not None:
                    self._edge_got[gi][j] = True
        self._edge_account(participating, failed)
        return losses

    # -- distillation fuse ----------------------------------------------------
    def _kd_fuse_device(self, edge_mats: List[jnp.ndarray]) -> List[jnp.ndarray]:
        """Fuse every edge's per-group models on its public shard (device
        pipeline: batches gathered from the public store in one call)."""
        n = self.assignment.shape[1]
        idx = draw_public_batches(self.rng, self.public_store.sizes, self.distill)
        xb = self.public_store.gather(np.arange(n), idx)[0]  # (E, steps, B, *feat)
        fused, _ = distill_fuse_flat(
            self.groups, [pk.spec for pk in self.packs], edge_mats, xb,
            self.distill, telemetry=self.tel,
        )
        return fused

    def _kd_fuse_host(self, edge_rows: List[List[jnp.ndarray]]) -> List[List[jnp.ndarray]]:
        """Host-pipeline counterpart: same flat fuse over stacked rows."""
        mats = [jnp.stack(rows) for rows in edge_rows]
        fused = self._kd_fuse_device(mats)
        return [[mat[j] for j in range(mat.shape[0])] for mat in fused]

    def _central_step(self):
        self.central_params = central_reference_step(
            self.central_params, self.central_data, self.rng, self.central_batch,
            self.program,
        )

    def _apply_server_momentum(
        self, old_rows: List[jnp.ndarray], new_rows: List[jnp.ndarray]
    ) -> List[jnp.ndarray]:
        """Cloud momentum in delta form per group row:
        ``v <- mu*v + (new - old); out = old + v``.  A group whose global
        row stood (fully starved under faults — ``new is old``) skips the
        velocity update rather than decaying it with a zero delta, matching
        the reference's degraded-mode 'global model stands' semantics."""
        if not self.server_momentum:
            return new_rows
        if self._srv_vel is None:
            self._srv_vel = [jnp.zeros_like(r) for r in new_rows]
        mu = self.server_momentum
        out = []
        for g, (old, new) in enumerate(zip(old_rows, new_rows)):
            if new is old:
                out.append(old)
                continue
            v = mu * self._srv_vel[g] + (new - old)
            self._srv_vel[g] = v
            out.append(old + v)
        return out

    def run(self, cloud_rounds: int, eval_every: int = 1) -> SimResult:
        with self.tel.watch_gc():
            return self._run(cloud_rounds, eval_every)

    def _run(self, cloud_rounds: int, eval_every: int) -> SimResult:
        n = self.assignment.shape[1]
        n_groups = len(self.groups)
        history: List[RoundMetrics] = []
        global_rows = [
            pk.ravel(t) for pk, t in zip(self.packs, self.group_params)
        ]
        edge_sizes = group_edge_sizes(self.clients, self.assignment, self.group_of)
        cloud_bits = None if n_groups == 1 else float(sum(self._group_bits))
        engine_name = f"sync-{self.pipeline}"
        comm = CommDelta(self.accountant) if self.tel.enabled else None
        wall_accum = sim_accum = 0.0
        for b in range(1, cloud_rounds + 1):
            t_round = time.perf_counter()
            sim0 = self.clock.seconds if self.clock is not None else 0.0
            self._round = b
            acc = None
            losses: List = []
            gc0 = self.tel.gc_seconds
            with self.tel.span("cloud_round", round=b, engine=engine_name) as rsp:
                if self.faults is not None:
                    self._maybe_repair(b)
                    if self.faults.spec.reassign:
                        edge_sizes = group_edge_sizes(
                            self.clients, self.assignment, self.group_of
                        )
                    self._edge_got = [
                        np.zeros(n, bool) for _ in range(n_groups)
                    ]
                    if self.clock is not None:
                        # the straggler model reads the round's faded channel
                        self.clock.latency = self.faults.latency(b)
                if self.pipeline == "device":
                    edge_mats = self._broadcast_rows(global_rows, n)
                    for k in range(self.schedule.edge_per_cloud):
                        self._er = k + 1
                        edge_mats, chunks = self._edge_round_device(edge_mats)
                        losses += chunks  # per-cohort (C,) arrays, still on device
                    if self.distill is not None:
                        edge_mats = self._kd_fuse_device(edge_mats)
                    # cloud FedAvg straight off the (E, D) matrices: static
                    # shape, no per-round stacking; one reduction per group
                    with self.tel.span(
                        "cloud_reduce", round=b, groups=n_groups, edges=n
                    ) as sp:
                        cost = self.tel.jit_cost(
                            "cloud_reduce",
                            self._cloud_mean,
                            edge_mats[0], np.asarray(edge_sizes[0], np.float32),
                        )
                        if cost:
                            sp.set(**cost)
                        if self.faults is not None:
                            # degraded-mode reduction: starved edges (no
                            # upload all cloud round) weigh zero; a fully
                            # starved group keeps its global row
                            gw = [
                                np.asarray(edge_sizes[g], np.float32)
                                * self._edge_got[g]
                                for g in range(n_groups)
                            ]
                            new_rows = [
                                self._cloud_mean(edge_mats[g], gw[g])
                                if gw[g].any()
                                else global_rows[g]
                                for g in range(n_groups)
                            ]
                        else:
                            new_rows = [
                                self._cloud_mean(edge_mats[g], edge_sizes[g])
                                for g in range(n_groups)
                            ]
                        global_rows = self._apply_server_momentum(
                            global_rows, new_rows
                        )
                    with self.tel.span("fetch", what="losses"):
                        losses = (
                            list(np.concatenate([np.asarray(c) for c in losses]))
                            if losses
                            else []
                        )
                else:
                    edge_rows = [[row] * n for row in global_rows]
                    for k in range(self.schedule.edge_per_cloud):
                        self._er = k + 1
                        losses += self._edge_round(edge_rows)
                    if self.distill is not None:
                        edge_rows = self._kd_fuse_host(edge_rows)
                    with self.tel.span("cloud_reduce", round=b, groups=n_groups, edges=n):
                        if self.faults is not None:
                            gw = [
                                np.asarray(edge_sizes[g], np.float32)
                                * self._edge_got[g]
                                for g in range(n_groups)
                            ]
                            new_rows = [
                                self._mean(edge_rows[g], gw[g])
                                if gw[g].any()
                                else global_rows[g]
                                for g in range(n_groups)
                            ]
                        else:
                            new_rows = [
                                self._mean(edge_rows[g], edge_sizes[g])
                                for g in range(n_groups)
                            ]
                        global_rows = self._apply_server_momentum(
                            global_rows, new_rows
                        )
                self.accountant.on_cloud_sync(n, bits=cloud_bits)
                if self.clock is not None:
                    self.clock.on_cloud_sync()
                serve_rec = (
                    self.serve.on_round(
                        b, lambda rows=global_rows: self.pack.unravel(rows[0])
                    )
                    if self.serve is not None
                    else None
                )
                div = 0.0
                if self.track_divergence:
                    for _ in range(self.schedule.cloud_period):
                        self._central_step()
                    div = weight_divergence(
                        self.pack.unravel(global_rows[0]), self.central_params
                    )
                if b % eval_every == 0 or b == cloud_rounds:
                    with self.tel.span("eval", round=b) as sp:
                        acc = float(
                            np.mean(
                                [
                                    self._evaluators[g](
                                        self.packs[g].unravel(global_rows[g])
                                    )
                                    for g in range(n_groups)
                                ]
                            )
                        )
                        sp.set(acc=acc)
                if self.tel.enabled:
                    rsp.set(gc_s=self.tel.gc_seconds - gc0)
            round_wall = time.perf_counter() - t_round
            round_sim = (self.clock.seconds - sim0) if self.clock is not None else 0.0
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
                if acc is not None:
                    self.tel.metrics.set_gauge("eval_acc", acc)
                self.tel.on_round(
                    engine=engine_name, round=b, acc=acc,
                    loss=float(np.mean(losses)) if losses else None,
                    wall_s=round_wall,
                    sim_s=round_sim if self.clock is not None else None,
                    **(serve_rec or {}),
                    **comm.take(),
                )
        trees = [pk.unravel(row) for pk, row in zip(self.packs, global_rows)]
        self.params = (
            trees[0] if n_groups == 1 else hetero_final_params(self.groups, trees)
        )
        result = SimResult(
            history, self.accountant, self.params,
            telemetry=self.tel if self.tel.enabled else None,
            serve_history=self.serve.history if self.serve is not None else None,
        )
        if self.clock is not None:
            result.wall_seconds = self.clock.seconds
        return result

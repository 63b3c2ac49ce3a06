"""Event-driven asynchronous HFL engine (straggler-tolerant edge rounds).

The synchronous simulators advance in lock-step: every edge round waits for
the slowest participating EU (the straggler effect of paper Sec. 4.2).  Here
each EU uploads when *it* finishes — completion times come from the
``channel.build_cost_matrices`` latency matrix — and an edge aggregates as
soon as a configurable quorum of its EUs has reported:

  * every upload is tagged with the edge-model version it started from;
    stale updates are down-weighted by ``staleness_decay ** staleness``
    (FedAsync-style, Xie et al. '19);
  * the current edge model anchors the average with the weight of the
    EUs that have NOT reported, so a full fresh quorum reduces exactly to
    FedAvg and the ``quorum=1.0, staleness_decay=1.0`` corner recovers
    synchronous semantics for single-connectivity assignments (modulo wall
    clock).  A DCA client is dispatched independently per edge — it trains
    once per membership from that edge's model — but its uplink is charged
    like the sync simulators': ONE multicast upload (~3% overhead) per
    dispatch, not a full uplink per membership, and uploads are charged at
    transmission time (dispatch), so stragglers dropped at the cloud
    barrier still spent their radio energy;
  * after ``edge_per_cloud`` aggregations an edge reports to the cloud; the
    cloud round closes when every edge has reported (the hierarchy's only
    barrier), and in-flight stragglers are dropped at that barrier.

Wall clock is the simulated event time itself, so ``SimResult.wall_seconds``
directly measures how much async buys over the synchronous max-latency model.

Device residency (ISSUE 2): edge models live in one (E, D) matrix (quorum
flushes write a row, the cloud barrier reduces the matrix in place with a
static shape), cohort batches are gathered from a ``DeviceShardStore``
instead of host-stacked numpy shards, and the tiny varying-N quorum
averages route through ``flat_mean``'s jitted contraction instead of
compiling a fresh pallas kernel per buffer size.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressionSpec
from repro.core.hfl import CommAccountant, HFLSchedule
from repro.data.synthetic_health import Dataset
from repro.engine.cohort import LocalJob, build_group_state, make_job, run_cohorts
from repro.engine.distill import (
    DistillSpec,
    check_distillable,
    check_public_shards,
    distill_fuse_flat,
    draw_public_batches,
)
from repro.engine.events import EventQueue
from repro.engine.flatten import BACKENDS, FlatPack, compress_flat_upload, flat_mean
from repro.engine.store import DeviceShardStore
from repro.federated.client import FLClient
from repro.federated.programs import as_program, group_edge_sizes
from repro.federated.simulation import (
    Evaluator,
    RoundMetrics,
    SimResult,
    hetero_final_params,
)
from repro.telemetry import NULL_TELEMETRY, coerce_telemetry
from repro.telemetry.report import CommDelta
from repro.utils.tree import tree_size_bytes


@dataclasses.dataclass
class _EdgeState:
    """Bookkeeping for one edge; the model itself lives as row ``j`` of the
    engine's (E, D) ``_edge_mat`` so the cloud mean and dispatch reads are
    fixed-shape device ops."""

    members: List[int]  # participating client indices this cloud round
    version: int = 0
    rounds_done: int = 0
    done_time: float = 0.0
    # buffered uploads: (client_idx, row, data_size, birth_version)
    buffer: List[Tuple[int, object, float, int]] = dataclasses.field(default_factory=list)
    # fault-injected runs: members whose upload to THIS edge was abandoned
    # (timeout / retries exhausted / battery death) — the quorum shrinks to
    # the live population; a later successful delivery re-registers the EU
    lost: set = dataclasses.field(default_factory=set)
    # whether any upload was aggregated this cloud round (a starved edge
    # contributes weight 0 to the degraded cloud reduction)
    got: bool = False


class AsyncHFLEngine:
    """Heap-scheduled async counterpart of :class:`BatchedSyncEngine`.

    Knobs (constructor):

    * ``program`` — any ``ClientProgram`` (``federated.PROGRAMS``: "cnn",
      "mlp", "lm", "moe", "mamba", "rwkv", or a "fedsgd" wrapper); a bare
      ``CNNConfig`` is coerced.
    * ``latency`` — (M, N) per-EU upload latency in seconds (drives the
      event clock; usually ``scenario.cost.latency``).
    * ``quorum`` — fraction of an edge's members that must report before
      it aggregates, in (0, 1]; ``1.0`` waits for everyone.
    * ``staleness_decay`` — weight multiplier per edge-model version an
      upload is behind (``1.0`` = no decay; FedAsync-style down-weighting
      below 1).
    * ``backend`` — ``"pallas"`` | ``"reference"`` aggregation path.
    * ``compression`` — ``None`` | ``CompressionSpec``; per-(client, edge)
      error feedback, accountant counts compressed bits.  Takes precedence
      over the program's own uplink quantization.

    Per-client heterogeneous hyperparameters (``lr``, ``batch_size``,
    ``local_epochs``) are honored exactly as in the sync engines — each
    dispatch trains the client with its own tuple.

    Heterogeneous-model populations work too: clients carrying different
    programs split into architecture groups with one (E, D_g) edge matrix
    each, quorum flushes aggregate within groups, and — given
    ``public_shards`` + ``distill`` — the cloud barrier fuses each edge's
    group models by logit distillation before the per-group cloud
    reduction (``engine.distill``).
    """

    def __init__(
        self,
        clients: List[FLClient],
        assignment: np.ndarray,
        program,
        test: Dataset,
        latency: np.ndarray,  # (M, N) per-EU upload latency incl. compute, s
        schedule: HFLSchedule = HFLSchedule(1, 1),
        seed: int = 0,
        upp: float = 1.0,
        staleness_decay: float = 0.5,
        quorum: float = 0.75,
        backhaul_s: float = 0.05,
        backend: str = "pallas",
        compression: Optional[CompressionSpec] = None,
        public_shards: Optional[List[Dataset]] = None,
        distill: Optional[DistillSpec] = None,
        faults=None,
        telemetry=None,
        cohort=None,
        server_momentum: float = 0.0,
        serve=None,
    ):
        if not (0.0 < quorum <= 1.0):
            raise ValueError(f"quorum must be in (0, 1], got {quorum}")
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        self.clients = clients
        self.assignment = np.asarray(assignment)
        self.program = as_program(program)  # bare CNNConfig still accepted
        self.test = test
        self.latency = np.asarray(latency)
        self.schedule = schedule
        self.rng = np.random.default_rng(seed)
        self.upp = upp
        # per-round cohort sampling (keyed side-channel draws, engine RNG
        # untouched).  The async engine dispatches once per CLOUD round, so
        # the cohort is drawn at edge-round key 1 — the same members the
        # sync engines would draw for their first edge round.
        self.cohort = cohort
        if cohort is not None and upp != 1.0:
            raise ValueError(
                "cohort sampling and UPP are both participation models; "
                "use upp=1.0 with a CohortSpec"
            )
        # cloud-side momentum on the aggregated delta (0.0 = plain FedAvg)
        self.server_momentum = float(server_momentum)
        self._srv_vel = None
        self.staleness_decay = staleness_decay
        self.quorum = quorum
        self.backhaul_s = backhaul_s
        self.backend = backend
        self.compression = compression
        self.params = self.program.init(jax.random.PRNGKey(seed))
        self.pack = FlatPack(self.params)
        # architecture groups (heterogeneous-model federation): one edge
        # matrix, pack, and payload per distinct client program
        gs = build_group_state(
            clients, self.program, self.params, self.pack, seed, compression
        )
        self.groups, self.group_of = gs.programs, gs.group_of
        self.group_params, self.packs = gs.params, gs.packs
        self._group_bits, self._uplink_bits = gs.bits, gs.uplink_bits
        # evaluation-under-traffic hook (serving.traffic.ServeTraffic): reads
        # the post-barrier global tree; side-channel draws keep serve=None
        # trajectories bit-identical to serve-on runs
        self.serve = serve
        if serve is not None and len(self.groups) > 1:
            raise ValueError(
                "serve traffic targets THE global model; heterogeneous-model "
                "populations have one per architecture group"
            )
        self.distill = distill if len(self.groups) > 1 else None
        self.public_store = None
        if self.distill is not None:
            check_public_shards(public_shards, self.assignment.shape[1])
            check_distillable(self.groups)
            self.public_store = DeviceShardStore.from_shards(public_shards)
        self.accountant = CommAccountant(model_bits=tree_size_bytes(self.params) * 8)
        # fault injection (repro.faults.FaultState); None = the historical
        # fault-free path, bit-identical to the golden trajectories
        self.faults = faults
        self._lat = self.latency  # per-round faded latency under faults
        self._client_edges: Dict[int, List[int]] = {}
        # per-client compression error feedback (a client trains ONCE per
        # dispatch and multicasts the same row, so the error state is
        # per-client, not per-(client, edge))
        self._errors: Dict[int, object] = {}
        self.queue = EventQueue()
        self._losses: List[float] = []
        # per-group edge models, each one (E, D_g) device matrix (_EdgeState)
        self._edge_mats: Optional[List[jnp.ndarray]] = None
        # None when shard sizes are skewed enough that padding would cost
        # more memory than the device gather saves; run_cohorts then falls
        # back to host batch stacking
        self.store = DeviceShardStore.build_if_economical(clients)
        self.tel = coerce_telemetry(telemetry) or NULL_TELEMETRY
        self._evaluators = [Evaluator(p, test, telemetry=self.tel) for p in self.groups]
        self._round = 0
        if self.tel.enabled:
            counts = np.bincount(self.group_of, minlength=len(self.groups))
            for g, prog in enumerate(self.groups):
                self.tel.metrics.set_gauge(f"group_clients/{prog.name}", int(counts[g]))

    # -- helpers --------------------------------------------------------------
    def _mean(self, rows: List, weights: List[float]):
        return flat_mean(
            jnp.stack(rows), np.asarray(weights, np.float32), backend=self.backend
        )

    def _apply_server_momentum(
        self, old_rows: List[jnp.ndarray], new_rows: List[jnp.ndarray]
    ) -> List[jnp.ndarray]:
        """Cloud momentum in delta form per group row (see the sync engine's
        counterpart); a row that stood under faults skips the velocity
        update instead of decaying it."""
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


    def _dispatch(self, client_ids: List[int], edges: Dict[int, _EdgeState]):
        """Train each client ONCE, multicast its row to every member edge.

        A DCA client trains a single local pass per dispatch — starting
        from the mean of its member edges' current models, the synchronous
        simulators' DCA start semantics — and the resulting update row is
        delivered to every member edge, matching the multicast uplink the
        accountant already charged (one transmission, ~3% overhead).
        Clients are processed in index order so the numpy RNG stream is
        consumed client-by-client like the synchronous simulators; in the
        ``quorum=1.0`` corner this makes async reduce to reference FedAvg.
        """
        client_ids = sorted(client_ids)
        if self.faults is not None:
            alive = self.faults.alive()
            live = []
            for i in client_ids:
                if alive[i]:
                    live.append(i)
                else:
                    # battery-dead EU: it never transmits; its edges stop
                    # waiting for it (the quorum shrinks to the live set)
                    for j in self._client_edges[i]:
                        edges[j].lost.add(i)
                    if self.tel.enabled:
                        self.tel.metrics.inc("faults_dead_skips")
            client_ids = live
        jobs: List[LocalJob] = []
        for i in client_ids:
            g = int(self.group_of[i])
            js = self._client_edges[i]
            # SCA: a direct row read (bit-identical to the historical
            # per-pair dispatch); DCA: the mean of the member edges' models
            start = (
                self._edge_mats[g][js[0]]
                if len(js) == 1
                else self._mean(
                    [self._edge_mats[g][j] for j in js], [1.0] * len(js)
                )
            )
            jobs.append(
                make_job(
                    self.clients[i], start, self.rng,
                    self.schedule.local_steps, tag=i,
                )
            )
        trained = run_cohorts(
            jobs, self.program, self.pack, store=self.store, telemetry=self.tel
        )
        compressing = self.compression is not None and self.compression.kind != "none"
        for i, job in zip(client_ids, jobs):
            g = int(self.group_of[i])
            js = self._client_edges[i]
            upd = trained.row(i)
            self._losses.append(trained.loss[i])
            program = self.clients[i].program
            if not compressing and program.quantizes_upload:
                upd = program.quantize_upload(job.start_flat, upd)
            else:
                upd = compress_flat_upload(
                    self.compression, self._errors, i, job.start_flat, upd
                )
            # each member edge sent this client a downlink model copy; the
            # uplink is ONE multicast on a shared resource share (paper:
            # ~3% overhead), not a full uplink per membership
            bits = self._uplink_bits[g]
            mc = self.accountant.dca_multicast_overhead if len(js) > 1 else 0.0
            self.accountant.on_eu_exchange(i, down_bits=self._group_bits[g] * len(js))
            if self.faults is None:
                self.accountant.on_eu_exchange(i, up_bits=bits * (1.0 + mc))
                for j in js:
                    self.queue.push(
                        self.queue.now + float(self._lat[i, j]),
                        "upload", client=i, edge=j, row=upd,
                        birth=edges[j].version,
                    )
                    if self.tel.enabled:
                        # simulated-time track: the radio upload occupies
                        # the event clock from dispatch until the edge
                        # hears it
                        self.tel.sim_span(
                            "upload",
                            self.queue.now,
                            self.queue.now + float(self._lat[i, j]),
                            tid=j + 1, client=i, edge=j,
                        )
            else:
                self._transmit(i, js, upd, edges, bits * (1.0 + mc), bits)

    def _transmit(
        self, i: int, js: List[int], upd, edges: Dict[int, _EdgeState],
        mcast_bits: float, unicast_bits: float,
    ) -> None:
        """One multicast transmission under the fault model.

        Every member edge's retry-with-exponential-backoff cascade is
        resolved at dispatch time (``FaultState.plan_upload``) and turned
        into one future "upload" or "lost" event.  Useful bits are charged
        when at least one edge hears the multicast; a fully-abandoned
        multicast and every retransmission land in the wasted-bits ledger.
        """
        b = self._round
        # attempt 0 is the shared multicast: one debit, costliest edge
        self.faults.debit(i, self.faults.upload_energy(b, i, np.asarray(js)))
        t0 = self.queue.now
        delivered = 0
        for j in js:
            plan = self.faults.plan_upload(b, i, j, float(self._lat[i, j]))
            if self.tel.enabled:
                for (s, e, a) in plan.windows:
                    self.tel.sim_span(
                        "upload" if a == 0 else "retry",
                        t0 + s, t0 + e, tid=j + 1, client=i, edge=j, attempt=a,
                    )
                if plan.retries:
                    self.tel.metrics.inc("faults_retries", plan.retries)
            for _ in range(plan.retries):
                self.accountant.on_wasted_upload(i, unicast_bits, kind="retry")
            if plan.ok:
                delivered += 1
                self.queue.push(
                    t0 + plan.t_end, "upload", client=i, edge=j, row=upd,
                    birth=edges[j].version,
                )
            else:
                if self.tel.enabled:
                    self.tel.sim_span(
                        "abandon", t0 + plan.t_end, t0 + plan.t_end,
                        tid=j + 1, client=i, edge=j, reason=plan.reason,
                    )
                    self.tel.metrics.inc(f"faults_abandon_{plan.reason}")
                self.queue.push(
                    t0 + plan.t_end, "lost", client=i, edge=j,
                    reason=plan.reason,
                )
        if delivered:
            self.accountant.on_eu_exchange(i, up_bits=mcast_bits)
        else:
            self.accountant.on_wasted_upload(i, mcast_bits, kind="abandoned")

    def _quorum_count(self, edge: _EdgeState) -> int:
        # quorum relaxation: abandoned members do not count toward the
        # population the edge waits on (edge.lost is empty when faults=None)
        return max(1, int(np.ceil(self.quorum * (len(edge.members) - len(edge.lost)))))

    def _settle(self, j: int, edge: _EdgeState, edges: Dict[int, _EdgeState]) -> None:
        """Flush the edge if its buffer now satisfies the (live) quorum."""
        if len(edge.buffer) >= self._quorum_count(edge):
            self._dispatch(self._edge_aggregate(j, edge), edges)

    def _drain_starved(self, edges: Dict[int, _EdgeState]) -> None:
        """The queue is empty but edges are unfinished (fault-injected runs
        only): nothing is in flight any more, so relax the quorum to
        whoever delivered (degraded flush) and mark delivery-less edges as
        starved — they stop waiting, and the degraded cloud reduction
        skips their contribution."""
        for j, edge in edges.items():
            if edge.rounds_done >= self.schedule.edge_per_cloud:
                continue
            if edge.buffer:
                if self.tel.enabled:
                    self.tel.metrics.inc("faults_degraded_flush")
                self._dispatch(self._edge_aggregate(j, edge), edges)
            else:
                edge.rounds_done = self.schedule.edge_per_cloud
                edge.done_time = self.queue.now
                if self.tel.enabled:
                    self.tel.metrics.inc("faults_starved_edges")

    def _maybe_repair(self, b: int) -> None:
        """Re-repair the assignment when channel drift invalidated memberships."""
        if not self.faults.spec.reassign:
            return
        new_lam, changed = self.faults.repair(b, self.assignment)
        if len(changed):
            self.assignment = new_lam
            if self.tel.enabled:
                self.tel.metrics.inc("faults_reassigned", int(len(changed)))

    def _edge_aggregate(self, j: int, edge: _EdgeState) -> List[int]:
        """Staleness-weighted aggregation; returns client redispatches.

        Group-aware: buffered uploads are averaged WITHIN each architecture
        group (a CNN row cannot average with an MLP row), each group's
        current edge model anchoring for that group's unreported members.
        The quorum itself counts reporters across every group — the edge
        flushes when enough of its EUs answered, whatever they train.
        """
        tel = self.tel
        with tel.span(
            "edge_aggregate",
            engine="async",
            edge=j,
            round=self._round,
            buffered=len(edge.buffer),
            version=edge.version,
        ):
            all_reporters = []
            for g in range(len(self.groups)):
                rows, weights, reporters = [], [], []
                for i, row, size, birth in sorted(edge.buffer, key=lambda b: b[0]):
                    if int(self.group_of[i]) != g:
                        continue
                    staleness = edge.version - birth
                    if tel.enabled:
                        tel.metrics.observe("async_staleness", float(staleness))
                    rows.append(row)
                    weights.append(max(size, 1.0) * self.staleness_decay ** staleness)
                    reporters.append(i)
                if not rows:
                    continue  # nothing from this architecture: its model stands
                # the current edge model stands in for the EUs that have not
                # reported (of this group)
                missing = [
                    i for i in edge.members
                    if int(self.group_of[i]) == g and i not in set(reporters)
                ]
                anchor_w = float(sum(max(self.clients[i].data_size, 1.0) for i in missing))
                if anchor_w > 0:
                    rows = [self._edge_mats[g][j]] + rows
                    weights = [anchor_w] + weights
                # quorum flushes average 1-3 rows; flat_mean routes these tiny-N
                # calls to a jitted contraction, so varying buffer sizes do not
                # compile a fresh pallas kernel per shape
                self._edge_mats[g] = self._edge_mats[g].at[j].set(self._mean(rows, weights))
                all_reporters += reporters
        if edge.buffer:
            edge.got = True
        edge.version += 1
        edge.rounds_done += 1
        edge.buffer = []
        self.accountant.on_edge_round()
        if edge.rounds_done >= self.schedule.edge_per_cloud:
            edge.done_time = self.queue.now
            return []
        # multicast semantics: a redispatched client trains once and uploads
        # to ALL its member edges (deduped — a client can buffer twice)
        return sorted(set(all_reporters))

    # -- main loop ------------------------------------------------------------
    def run(self, cloud_rounds: int, eval_every: int = 1) -> SimResult:
        m, n = self.assignment.shape
        n_groups = len(self.groups)
        history: List[RoundMetrics] = []
        global_rows = [pk.ravel(t) for pk, t in zip(self.packs, self.group_params)]
        edge_sizes = group_edge_sizes(self.clients, self.assignment, self.group_of)
        cloud_bits = None if n_groups == 1 else float(sum(self._group_bits))
        tel = self.tel
        comm = CommDelta(self.accountant) if tel.enabled else None
        wall_accum = sim_accum = 0.0
        for b in range(1, cloud_rounds + 1):
            t_round = time.perf_counter()
            sim0 = self.queue.now
            self._round = b
            acc = None
            with tel.span("cloud_round", engine="async", round=b):
                self._losses = []
                if self.faults is not None:
                    self._maybe_repair(b)
                    if self.faults.spec.reassign:
                        edge_sizes = group_edge_sizes(
                            self.clients, self.assignment, self.group_of
                        )
                    # retry deadlines and the event clock read the round's
                    # faded channel
                    self._lat = self.faults.latency(b)
                with tel.span("assignment", round=b) as sp:
                    if self.cohort is not None:
                        participating = self.cohort.mask(
                            b, 1, assignment=self.assignment
                        )
                    else:
                        participating = self.rng.random(m) < self.upp
                        if not participating.any():
                            participating[self.rng.integers(0, m)] = True
                    if self.faults is not None:
                        participating &= self.faults.participation(b)
                    # every edge starts the cloud round from its group's
                    # global model
                    self._edge_mats = [
                        jnp.broadcast_to(row, (n, row.shape[0])) for row in global_rows
                    ]
                    edges: Dict[int, _EdgeState] = {}
                    for j in range(n):
                        members = [
                            i
                            for i in range(m)
                            if self.assignment[i, j] and participating[i]
                        ]
                        st = _EdgeState(members=members)
                        if not members:  # nothing to wait for: report immediately
                            st.rounds_done = self.schedule.edge_per_cloud
                            st.done_time = self.queue.now
                        edges[j] = st
                    client_ids = [
                        i for i in range(m)
                        if participating[i] and self.assignment[i].any()
                    ]
                    self._client_edges = {
                        i: [int(j) for j in np.nonzero(self.assignment[i])[0]]
                        for i in client_ids
                    }
                    sp.set(
                        participating=int(participating.sum()),
                        pairs=sum(len(v) for v in self._client_edges.values()),
                    )
                if tel.enabled:
                    tel.metrics.set_gauge("participating", int(participating.sum()))
                self._dispatch(client_ids, edges)
                while any(
                    e.rounds_done < self.schedule.edge_per_cloud for e in edges.values()
                ):
                    if not self.queue:
                        if self.faults is None:
                            raise RuntimeError(
                                "async engine deadlock: no pending events"
                            )
                        self._drain_starved(edges)
                        continue
                    ev = self.queue.pop()
                    j = ev.payload["edge"]
                    edge = edges[j]
                    if edge.rounds_done >= self.schedule.edge_per_cloud:
                        continue  # late straggler: edge already reported to cloud
                    if ev.kind == "lost":
                        # abandoned upload: shrink the quorum population and
                        # re-check whether the buffer now satisfies it
                        edge.lost.add(ev.payload["client"])
                        self._settle(j, edge, edges)
                        continue
                    edge.buffer.append(
                        (
                            ev.payload["client"],
                            ev.payload["row"],
                            float(self.clients[ev.payload["client"]].data_size),
                            ev.payload["birth"],
                        )
                    )
                    edge.lost.discard(ev.payload["client"])
                    self._settle(j, edge, edges)
                if self.faults is not None:
                    self.faults.record_gauges(tel)
                # cloud barrier: all edges reported; drop in-flight stragglers
                self.queue.clear()
                self.queue.now = (
                    max(e.done_time for e in edges.values()) + self.backhaul_s
                )
                if tel.enabled:
                    # the same cloud round on the SIMULATED-time track: from
                    # its first dispatch to the post-barrier backhaul
                    tel.sim_span("cloud_round", sim0, self.queue.now, round=b)
                if self.distill is not None:
                    # fuse each edge's per-group models on its public shard
                    # before the cloud reduces per group (edge-local: costs no
                    # EU traffic, only the barrier's wall-clock headroom)
                    idx = draw_public_batches(
                        self.rng, self.public_store.sizes, self.distill
                    )
                    xb = self.public_store.gather(np.arange(n), idx)[0]
                    self._edge_mats, _ = distill_fuse_flat(
                        self.groups, [pk.spec for pk in self.packs],
                        self._edge_mats, xb, self.distill,
                        telemetry=tel,
                    )
                # cloud FedAvg straight off the (E, D) matrices: static shape,
                # one reduction per architecture group
                with tel.span("cloud_reduce", round=b, edges=n, groups=n_groups) as sp:
                    cost = tel.jit_cost(
                        "cloud_reduce",
                        lambda u, w: flat_mean(u, w, backend=self.backend),
                        self._edge_mats[0],
                        np.asarray(edge_sizes[0], np.float32),
                    )
                    if cost:
                        sp.set(**cost)
                    if self.faults is not None:
                        # degraded-mode reduction: starved edges (no upload
                        # aggregated all cloud round) weigh zero; a fully
                        # starved hierarchy keeps the global model
                        got = np.array([edges[j].got for j in range(n)], bool)
                        gw = [
                            np.asarray(edge_sizes[g], np.float32) * got
                            for g in range(n_groups)
                        ]
                        new_rows = [
                            flat_mean(self._edge_mats[g], gw[g], backend=self.backend)
                            if gw[g].any()
                            else global_rows[g]
                            for g in range(n_groups)
                        ]
                    else:
                        new_rows = [
                            flat_mean(
                                self._edge_mats[g],
                                np.asarray(edge_sizes[g], np.float32),
                                backend=self.backend,
                            )
                            for g in range(n_groups)
                        ]
                    global_rows = self._apply_server_momentum(global_rows, new_rows)
                self.accountant.on_cloud_sync(n, bits=cloud_bits)
                serve_rec = (
                    self.serve.on_round(
                        b, lambda rows=global_rows: self.packs[0].unravel(rows[0])
                    )
                    if self.serve is not None
                    else None
                )
                if b % eval_every == 0 or b == cloud_rounds:
                    with tel.span("eval", round=b) as sp:
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
            round_wall = time.perf_counter() - t_round
            round_sim = self.queue.now - sim0
            wall_accum += round_wall
            sim_accum += round_sim
            if acc is not None:
                history.append(
                    RoundMetrics(
                        b,
                        acc,
                        0.0,
                        float(np.mean(self._losses)) if self._losses else 0.0,
                        wall_seconds=wall_accum,
                        sim_seconds=sim_accum,
                    )
                )
                wall_accum = sim_accum = 0.0
            if tel.enabled:
                if acc is not None:
                    tel.metrics.set_gauge("eval_acc", acc)
                tel.on_round(
                    engine="async",
                    round=b,
                    acc=acc,
                    loss=float(np.mean(self._losses)) if self._losses else None,
                    wall_s=round_wall,
                    sim_s=round_sim,
                    **(serve_rec or {}),
                    **comm.take(),
                )
        trees = [pk.unravel(row) for pk, row in zip(self.packs, global_rows)]
        self.params = (
            trees[0] if n_groups == 1 else hetero_final_params(self.groups, trees)
        )
        return SimResult(
            history,
            self.accountant,
            self.params,
            wall_seconds=self.queue.now,
            telemetry=tel if tel.enabled else None,
            serve_history=self.serve.history if self.serve is not None else None,
        )

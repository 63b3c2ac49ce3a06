"""Streaming synchronous engine: population M as a streaming axis.

``BatchedSyncEngine`` materializes the population — M ``FLClient``
objects, an (M, N) assignment matrix, the full (M, n_max, *feat) device
store — which caps it around M≈2048.  ``StreamSyncEngine`` holds only
O(M) *small integer metadata* (the source's (M,) shard sizes, the (M,)
``edge_of`` assignment, the plan's (M,) step buckets — a few int64 arrays,
~24 bytes/client) plus O(cohort) everything else:

  * clients come from a lazy :class:`~repro.data.shard_source.ShardSource`
    (``shard(cid)`` pure in ``(seed, cid)``), paged onto the device through
    a bounded :class:`~repro.engine.store.PagedShardStore`;
  * every round trains only a :class:`~repro.federated.sampling.CohortSpec`
    cohort — the per-round python cost is O(cohort), never O(M);
  * edge FedAvg renormalizes over the *sampled* members via the same
    ``_segment_agg_keep`` weights machinery the sync engine uses for UPP
    and fault masks (PR 7) — edges with no sampled member keep their model;
  * the accountant is charged with a compact (cohort, N) matrix carrying
    true client ids (``row_ids``), so traffic totals and per-EU attribution
    match what the materialized engine would have recorded for the same
    cohorts.

Scope: SCA assignment (compact ``edge_of``; DCA needs pair structure that
is O(M·N)), one homogeneous program, no compression/faults (both are
per-client-state models — they compose with *materialized* cohort runs via
``BatchedSyncEngine(cohort=...)``).  RNG parity: the cohort draw comes
from the spec's keyed side-channel generator and batch indices consume the
engine RNG per member in ascending client order — draw-for-draw what
``BatchedSyncEngine`` consumes for the same member set, so stream and sync
cohort runs share one trajectory (see tests/test_stream.py).
"""
from __future__ import annotations

import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hfl import CommAccountant, HFLSchedule
from repro.data.synthetic_health import Dataset
from repro.engine.cohort import StreamCohortPlan, _cohort_epoch_flat
from repro.engine.flatten import BACKENDS, FlatPack, flat_mean
from repro.engine.store import PagedShardStore, _store_gather
from repro.federated.programs import as_program
from repro.federated.sampling import CohortSpec
from repro.federated.simulation import Evaluator, RoundMetrics, SimResult
from repro.telemetry import NULL_TELEMETRY, coerce_telemetry
from repro.telemetry.report import CommDelta
from repro.utils.tree import tree_size_bytes
from functools import partial


@partial(jax.jit, static_argnames=("n_segments",))
def _segment_sums(upd, seg, w, n_segments: int):
    """Weighted per-segment numerator/denominator for one cohort group.

    The materialized engines aggregate with one ``_segment_agg_keep`` over
    the concatenated update matrix; here each group's rows are padded to a
    power of two, so concatenating them would produce a per-round zoo of
    shapes and a recompile each.  Summing per group (a handful of stable
    shapes) and dividing once is the same weighted mean — padded rows carry
    weight zero and cannot contribute.
    """
    return (
        jax.ops.segment_sum(upd * w[:, None], seg, num_segments=n_segments),
        jax.ops.segment_sum(w, seg, num_segments=n_segments),
    )


@jax.jit
def _edge_agg_finish(num, den, has, prev):
    """num/den per edge; zero-weight edges give 0 like ``flat_segment_mean``,
    and edges with no sampled member keep their previous model (``has``)."""
    mean = jnp.where(den[:, None] > 0, num / jnp.maximum(den, 1e-30)[:, None], 0.0)
    return jnp.where(has[:, None], mean, prev)


class StreamSyncEngine:
    """Synchronous two-level FedAvg over a lazy population.

    ``source`` is a ShardSource; ``edge_of`` an (M,) int array mapping each
    client to its edge (SCA; -1 = unattached).  ``cohort`` is required —
    full participation over a streaming population is exactly the case the
    engine exists to avoid (use ``BatchedSyncEngine`` when M fits).
    """

    def __init__(
        self,
        source,
        edge_of: np.ndarray,
        program,
        test: Dataset,
        cohort: CohortSpec,
        n_edges: Optional[int] = None,
        schedule: HFLSchedule = HFLSchedule(1, 1),
        seed: int = 0,
        backend: str = "pallas",
        page_slots: Optional[int] = None,
        batch_size: int = 10,
        lr: float = 1e-3,
        max_steps: int = 128,
        server_momentum: float = 0.0,
        telemetry=None,
    ):
        if not isinstance(cohort, CohortSpec):
            raise ValueError("StreamSyncEngine requires a CohortSpec cohort")
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        self.source = source
        # all O(M) state is 4-byte ints/floats, computed chunked: the
        # engine's whole M-proportional footprint is ~16 bytes/client
        self.edge_of = np.ascontiguousarray(edge_of, np.int32)
        self.m = len(self.edge_of)
        if self.m != source.n_clients:
            raise ValueError("edge_of length != source.n_clients")
        self.n_edges = (
            int(n_edges) if n_edges is not None else int(self.edge_of.max()) + 1
        )
        self.program = as_program(program)
        self.test = test
        self.cohort = cohort
        self.schedule = schedule
        self.backend = backend
        self.rng = np.random.default_rng(seed)
        self.params = self.program.init(jax.random.PRNGKey(seed))
        self.pack = FlatPack(self.params)
        self._sizes = np.asarray(source.sizes)  # shared, no copy
        chunk = 1 << 16
        edge_sizes = np.zeros(self.n_edges, np.float64)
        n_eligible = 0
        for lo in range(0, self.m, chunk):
            eo = self.edge_of[lo : lo + chunk]
            att = eo >= 0
            n_eligible += int(att.sum())
            edge_sizes += np.bincount(
                eo[att],
                weights=self._sizes[lo : lo + chunk][att].astype(np.float64),
                minlength=self.n_edges,
            )
        if not n_eligible:
            raise ValueError("no client is attached to any edge")
        # None = every client attached: the cohort draw then samples ids
        # directly instead of through a materialized (M,) eligible list
        self.eligible = (
            None if n_eligible == self.m else np.flatnonzero(self.edge_of >= 0)
        )
        self._edge_sizes = edge_sizes.astype(np.float32)
        # every group is padded to one fixed row count: the compiled-shape
        # set is then {rows} x {step buckets}, independent of how a round's
        # draw happens to split across buckets
        self._rows = 1 << max(0, cohort.size - 1).bit_length()
        self.plan = StreamCohortPlan(
            source.sizes, self.program,
            batch_size=batch_size, lr=lr, max_steps=max_steps,
        )
        # working set: 2x the cohort so consecutive rounds' overlap pages
        # nothing, still O(cohort) device memory
        capacity = page_slots if page_slots is not None else 2 * cohort.size
        self.store = PagedShardStore(source, capacity=max(capacity, cohort.size))
        model_bits = tree_size_bytes(self.params) * 8
        self.accountant = CommAccountant(model_bits=model_bits)
        self._uplink_bits = self.program.uplink_bits(model_bits)
        self.server_momentum = float(server_momentum)
        self._srv_vel = None
        self.tel = coerce_telemetry(telemetry) or NULL_TELEMETRY
        self._evaluator = Evaluator(self.program, test, telemetry=self.tel)
        self._round = 0

    # -- one edge round over the sampled cohort ------------------------------
    def _edge_round(self, edge_mat: jnp.ndarray, b: int, er: int):
        tel = self.tel
        with tel.span("assignment", round=b, engine="sync-stream"):
            members = self.cohort.draw(
                b, er, eligible=self.eligible, edge_of=self.edge_of, m=self.m
            )
            groups, passthrough = self.plan.draw(
                self.rng, members, self.schedule.local_steps
            )
            if tel.enabled:
                tel.metrics.set_gauge("participating", len(members))
        num = jnp.zeros((self.n_edges, self.pack.dim), jnp.float32)
        den = jnp.zeros((self.n_edges,), jnp.float32)
        ids: List[np.ndarray] = []
        losses: List = []
        for g in groups:
            with tel.span(
                "cohort_epoch", round=b, program=g.program.name,
                clients=len(g.members), epochs=int(g.idx.shape[1]),
                steps=g.steps, batch=g.batch,
            ):
                # pad each group to the engine's fixed row count: per-round
                # fluctuation in how many members land in each step bucket
                # would otherwise retrace/recompile the jitted epoch and
                # gather every round.  Rows are vmap-independent, so padded
                # rows (slot/row 0 repeated, zero batch indices, weight 0)
                # cannot perturb real rows and never consume RNG draws.
                c = len(g.members)
                pad = self._rows - c
                slots = self.store.ensure(g.members)
                eo = self.edge_of[g.members]
                w = self._sizes[g.members].astype(np.float32)
                idx = g.idx
                if pad:
                    slots = np.concatenate([slots, np.repeat(slots[:1], pad)])
                    eo = np.concatenate([eo, np.repeat(eo[:1], pad)])
                    w = np.concatenate([w, np.zeros(pad, np.float32)])
                    idx = np.concatenate(
                        [idx, np.zeros((pad,) + idx.shape[1:], idx.dtype)]
                    )
                start = jnp.take(edge_mat, jnp.asarray(eo, jnp.int32), axis=0)
                flat = start
                slots_j = jnp.asarray(slots, jnp.int32)
                for e in range(idx.shape[1]):
                    xb, yb = _store_gather(
                        self.store.x, self.store.y, slots_j,
                        jnp.asarray(idx[:, e], jnp.int32),
                    )
                    flat, loss = _cohort_epoch_flat(
                        flat, xb, yb, self.pack.spec, self.program, g.steps, g.lr
                    )
                if self.program.quantizes_upload:
                    flat = self.program.quantize_upload(start, flat)
                gnum, gden = _segment_sums(
                    flat, jnp.asarray(eo, jnp.int32), jnp.asarray(w), self.n_edges
                )
                num = num + gnum
                den = den + gden
            ids.append(g.members)
            losses.append(np.asarray(loss)[:c])
        if len(passthrough):
            # empty shards participate with weight zero: they never move an
            # edge model, but they count for `has` and for accounting, same
            # as in the materialized engines
            ids.append(passthrough)
            losses.append(np.zeros(len(passthrough), np.float32))
        cids = np.concatenate(ids)
        seg = self.edge_of[cids]
        with tel.span(
            "edge_aggregate", round=b, clients=len(cids), edges=self.n_edges
        ):
            # sampled-member FedAvg: weights renormalize over the cohort,
            # edges with no sampled member keep their previous model
            has = np.bincount(seg, minlength=self.n_edges) > 0
            edge_mat = _edge_agg_finish(num, den, jnp.asarray(has), edge_mat)
        # compact cohort-only accounting with true client ids
        lam = np.zeros((len(cids), self.n_edges), np.int8)
        lam[np.arange(len(cids)), seg] = 1
        self.accountant.on_edge_sync(
            lam, uplink_bits=self._uplink_bits, row_ids=cids
        )
        return edge_mat, losses

    def run(self, cloud_rounds: int, eval_every: int = 1) -> SimResult:
        n = self.n_edges
        history: List[RoundMetrics] = []
        global_row = self.pack.ravel(self.params)
        comm = CommDelta(self.accountant) if self.tel.enabled else None
        wall_accum = 0.0
        for b in range(1, cloud_rounds + 1):
            t_round = time.perf_counter()
            self._round = b
            acc = None
            losses: List = []
            with self.tel.span("cloud_round", round=b, engine="sync-stream"):
                edge_mat = jnp.broadcast_to(global_row, (n, global_row.shape[0]))
                for k in range(self.schedule.edge_per_cloud):
                    edge_mat, chunks = self._edge_round(edge_mat, b, k + 1)
                    losses += chunks
                with self.tel.span("cloud_reduce", round=b, edges=n):
                    new_row = flat_mean(
                        edge_mat, self._edge_sizes, backend=self.backend
                    )
                    if self.server_momentum:
                        delta = new_row - global_row
                        self._srv_vel = (
                            delta
                            if self._srv_vel is None
                            else self.server_momentum * self._srv_vel + delta
                        )
                        global_row = global_row + self._srv_vel
                    else:
                        global_row = new_row
                self.accountant.on_cloud_sync(n)
                if b % eval_every == 0 or b == cloud_rounds:
                    with self.tel.span("eval", round=b) as sp:
                        acc = self._evaluator(self.pack.unravel(global_row))
                        sp.set(acc=acc)
            round_wall = time.perf_counter() - t_round
            wall_accum += round_wall
            loss_arr = (
                np.concatenate([np.asarray(c) for c in losses]) if losses else None
            )
            if acc is not None:
                history.append(
                    RoundMetrics(
                        b, acc, 0.0,
                        float(loss_arr.mean()) if loss_arr is not None else 0.0,
                        wall_seconds=wall_accum,
                    )
                )
                wall_accum = 0.0
            if self.tel.enabled:
                if acc is not None:
                    self.tel.metrics.set_gauge("eval_acc", acc)
                self.tel.metrics.set_gauge("page_hits", self.store.hits)
                self.tel.metrics.set_gauge("page_misses", self.store.misses)
                self.tel.metrics.set_gauge("page_evictions", self.store.evictions)
                self.tel.on_round(
                    engine="sync-stream", round=b, acc=acc,
                    loss=float(loss_arr.mean()) if loss_arr is not None else None,
                    wall_s=round_wall, sim_s=None, **comm.take(),
                )
        self.params = self.pack.unravel(global_row)
        return SimResult(
            history, self.accountant, self.params,
            telemetry=self.tel if self.tel.enabled else None,
        )

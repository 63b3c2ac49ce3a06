"""Mesh-parallel synchronous HFL engine: ``shard_map`` over the edge axis.

``MeshSyncEngine`` executes ``BatchedSyncEngine``'s device pipeline over a
real (or ``--xla_force_host_platform_device_count`` virtual) device mesh
built by ``repro.distributed.axes.edge_mesh``: a 1-D mesh whose ``"edge"``
axis carries the federation's edge nodes.  The mapping mirrors the paper's
communication structure (eqs. 8-9):

  * edge ``j`` lives on device ``j // (E / n_devices)`` and its EUs' cohort
    rows are laid out on the same device — local training and the per-edge
    FedAvg (``hier_segment_aggregate`` semantics) are DEVICE-LOCAL, so the
    T edge rounds per cloud round compile to programs with **zero**
    cross-edge collectives;
  * the cloud reduction is the only cross-edge collective: a two-stage
    weighted mean (per-device partial sums + ``psum`` over ``"edge"``)
    moving one model payload per cloud round — 1/T of the per-edge-round
    schedule, which is the paper's traffic claim, structurally.

``MeshCommLedger`` pins that claim in HLO: every mesh program is compiled
ahead of time, its post-SPMD text analyzed by ``distributed.hlo_stats``,
and per-program collective bytes (total + cross-edge) are tallied per call
— the compiled-code counterpart of ``CommAccountant``'s simulated bits.
``engine.comm_report()`` returns both, and ``benchmarks/distributed_bench``
writes them to ``BENCH_distributed.json``.

Semantics are the base engine's: the same numpy RNG stream (participation,
then per-client batch draws in global client order via ``CohortPlan``), the
same keyed ``CohortSpec`` side channel, the same accounting.  Per-device
row padding (power-of-two, weight-0 repeats of a real row) consumes no RNG,
so the mesh trajectory matches ``BatchedSyncEngine`` on every mesh size —
pinned <= 1e-6 (and golden-hashed per device count) by
``tests/test_hfl_mesh.py``.

Scope (raises otherwise): single-connectivity assignments (SCA), one
architecture group, no compression / upload quantization / fault injection.
Known constraint: virtual CPU devices share one thread pool, so off-TPU the
mesh path is a topology-correctness + comm-accounting tool, not a speedup.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hfl import HFLSchedule
from repro.distributed.axes import EDGE_AXIS, edge_mesh
from repro.distributed.hlo_stats import analyze, cross_edge_bytes
from repro.engine.cohort import _cohort_epoch_body
from repro.engine.flatten import ravel_batched, unravel_batched
from repro.engine.sync_sim import BatchedSyncEngine
from repro.kernels.ref import hier_segment_aggregate_ref


class MeshCommLedger:
    """Per-program HLO collective accounting for the mesh engine.

    Every distinct (program, arg shapes) pair is lowered and compiled ONCE
    (ahead of time — the analyzed HLO is exactly the executable that runs),
    its post-SPMD collective bytes classified by
    ``hlo_stats.cross_edge_bytes``, and every execution tallied, so
    ``report()`` can state measured cross-edge bytes per call and in total.
    """

    def __init__(self, devs_per_edge: int = 1, telemetry=None):
        self.devs_per_edge = devs_per_edge
        self.tel = telemetry
        self._compiled: Dict[tuple, object] = {}
        self._stats: Dict[tuple, Dict[str, float]] = {}
        self._calls: Dict[tuple, int] = {}

    def call(self, key: str, jitted_fn, *args):
        sig = (key, tuple((tuple(a.shape), str(a.dtype)) for a in args))
        ex = self._compiled.get(sig)
        if ex is None:
            ex = jitted_fn.lower(*args).compile()
            st = analyze(ex.as_text())
            self._compiled[sig] = ex
            self._stats[sig] = {
                "coll_bytes": float(st.total_coll()),
                "cross_edge_bytes": float(cross_edge_bytes(st, self.devs_per_edge)),
                "flops": float(st.flops),
            }
            if self.tel is not None and self.tel.enabled:
                self.tel.metrics.set_gauge(
                    f"mesh_coll_bytes/{key}", self._stats[sig]["coll_bytes"]
                )
                self.tel.metrics.set_gauge(
                    f"mesh_cross_edge_bytes/{key}", self._stats[sig]["cross_edge_bytes"]
                )
        self._calls[sig] = self._calls.get(sig, 0) + 1
        return ex(*args)

    def report(self) -> Dict[str, object]:
        programs: Dict[str, Dict[str, float]] = {}
        for sig, n in self._calls.items():
            key = sig[0]
            st = self._stats[sig]
            rec = programs.setdefault(
                key,
                {"calls": 0, "compiles": 0, "coll_bytes_per_call": 0.0,
                 "cross_edge_bytes_per_call": 0.0, "cross_edge_bytes_total": 0.0},
            )
            rec["calls"] += n
            rec["compiles"] += 1
            # per-call figures report the most recent compile's shape class
            rec["coll_bytes_per_call"] = st["coll_bytes"]
            rec["cross_edge_bytes_per_call"] = st["cross_edge_bytes"]
            rec["cross_edge_bytes_total"] += n * st["cross_edge_bytes"]
        return {
            "programs": programs,
            "cross_edge_total_bytes": sum(
                p["cross_edge_bytes_total"] for p in programs.values()
            ),
        }


@dataclasses.dataclass
class _MeshLayout:
    """Device-block row layout for one cohort: member ``c`` occupies row
    ``slot[c]`` inside the (k * rows_per_dev,)-padded arrays; pad rows
    repeat a real member with weight 0 (no RNG, no contribution)."""

    slot: np.ndarray  # (C,) padded-row index per member, member order
    src: np.ndarray  # (rows,) member index feeding each row (pads -> 0)
    members: np.ndarray  # (rows,) client ids (pads repeat members[0])
    seg: jnp.ndarray  # (rows,) int32 global edge ids, sharded
    w: jnp.ndarray  # (rows,) float32 aggregation weights, sharded


def _mesh_devices(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


class MeshSyncEngine(BatchedSyncEngine):
    """``BatchedSyncEngine`` with the round's device programs sharded over
    an ``edge_mesh`` (see module docstring).  ``mesh`` is a device count,
    a ready ``jax.sharding.Mesh`` with an ``"edge"`` axis, or ``None`` for
    the largest visible-device count that divides the edge count."""

    def __init__(
        self,
        clients,
        assignment,
        program,
        test,
        schedule: HFLSchedule = HFLSchedule(1, 1),
        seed: int = 0,
        upp: float = 1.0,
        track_divergence: bool = False,
        central_batch: int = 50,
        cost_latency=None,
        backend: str = "pallas",
        telemetry=None,
        cohort=None,
        server_momentum: float = 0.0,
        mesh: "Optional[int | Mesh]" = None,
        faults=None,
        compression=None,
        serve=None,
    ):
        if faults is not None:
            raise ValueError("MeshSyncEngine does not support fault injection")
        if compression is not None and getattr(compression, "kind", "none") != "none":
            raise ValueError("MeshSyncEngine does not support upload compression")
        super().__init__(
            clients, assignment, program, test, schedule=schedule, seed=seed,
            upp=upp, track_divergence=track_divergence, central_batch=central_batch,
            cost_latency=cost_latency, backend=backend, pipeline="device",
            telemetry=telemetry, cohort=cohort, server_momentum=server_momentum,
            serve=serve,
        )
        if len(self.groups) > 1:
            raise ValueError(
                "MeshSyncEngine supports one architecture group; "
                "use BatchedSyncEngine for model_mix populations"
            )
        if self.program.quantizes_upload:
            raise ValueError("MeshSyncEngine does not support upload quantization")
        if not self._single_edge:
            raise ValueError(
                "MeshSyncEngine requires single-connectivity (SCA) assignments"
            )
        n = self.assignment.shape[1]
        if isinstance(mesh, Mesh):
            if EDGE_AXIS not in mesh.axis_names:
                raise ValueError(f"mesh must carry an {EDGE_AXIS!r} axis")
            self.mesh = mesh
        elif mesh is None:
            k = min(len(jax.devices()), n)
            while n % k:
                k -= 1
            if k < len(jax.devices()):
                warnings.warn(
                    f"MeshSyncEngine: {n} edges do not divide over "
                    f"{len(jax.devices())} devices; running on {k}",
                    stacklevel=2,
                )
            self.mesh = edge_mesh(k)
        else:
            self.mesh = edge_mesh(int(mesh))
        self.n_devices = _mesh_devices(self.mesh)
        if n % self.n_devices:
            raise ValueError(
                f"edge count {n} must be divisible by mesh size {self.n_devices}"
            )
        self._epe = n // self.n_devices  # edges per device
        self._edge_ns = NamedSharding(self.mesh, P(EDGE_AXIS))
        self._ledger = MeshCommLedger(devs_per_edge=1, telemetry=self.tel)
        self._edge_rounds_done = 0
        self._cloud_syncs_done = 0
        self._epoch_fns: Dict[tuple, object] = {}
        self._build_programs()
        if self.tel.enabled:
            self.tel.metrics.set_gauge("mesh_devices", self.n_devices)
            self.tel.metrics.set_gauge("mesh_edges_per_device", self._epe)

    # -- sharded programs ---------------------------------------------------
    def _build_programs(self) -> None:
        epe = self._epe
        pe = P(EDGE_AXIS)

        def smap(fn, n_in, out_specs):
            return jax.jit(
                jax.shard_map(fn, mesh=self.mesh, in_specs=(pe,) * n_in,
                              out_specs=out_specs)
            )

        def _starts(edge_mat, eo):
            # SCA: each client's start row IS its edge's model (local gather)
            base = jax.lax.axis_index(EDGE_AXIS) * epe
            return jnp.take(edge_mat, eo - base, axis=0)

        def _agg_keep(edge_mat, upd, seg, w, has):
            # per-edge FedAvg over the device-local membership rows, exactly
            # the ``_segment_agg_keep`` math (normalize-then-scatter) so the
            # single-cohort round is bit-identical to the base engine
            base = jax.lax.axis_index(EDGE_AXIS) * epe
            agg = hier_segment_aggregate_ref(upd, seg - base, w, epe)
            return jnp.where(has[:, None], agg, edge_mat)

        def _seg_sums(upd, seg, w):
            # partial-sum form for multi-cohort rounds (hetero hyperparams /
            # passthrough uploads): accumulated across cohorts, then finished
            base = jax.lax.axis_index(EDGE_AXIS) * epe
            s = seg - base
            num = jax.ops.segment_sum(upd * w[:, None], s, num_segments=epe)
            den = jax.ops.segment_sum(w, s, num_segments=epe)
            return num, den

        def _finish(num, den, has, edge_mat):
            mean = jnp.where(
                den[:, None] > 0, num / jnp.maximum(den, 1e-30)[:, None], 0.0
            )
            return jnp.where(has[:, None], mean, edge_mat)

        def _cloud(edge_mat, w):
            # two-stage weighted mean; the psums are the ONLY cross-edge
            # collective in the whole round.  Matches ``_small_mean``'s
            # normalize-then-contract form (bit-identical at one device).
            wf = w.astype(jnp.float32)
            wsum = jax.lax.psum(jnp.sum(wf), EDGE_AXIS)
            wn = wf / jnp.maximum(wsum, 1e-30)
            part = jnp.tensordot(wn, edge_mat.astype(jnp.float32), axes=1,
                                 precision=jax.lax.Precision.HIGHEST)
            return jax.lax.psum(part, EDGE_AXIS).astype(edge_mat.dtype)

        self._starts_fn = smap(_starts, 2, pe)
        self._agg_keep_fn = smap(_agg_keep, 5, pe)
        self._seg_sums_fn = smap(_seg_sums, 3, (pe, pe))
        self._finish_fn = smap(_finish, 4, pe)
        self._cloud_fn = smap(_cloud, 2, P())

    def _epoch_fn(self, program, n_steps: int, lr: float):
        key = (program, n_steps, lr)
        fn = self._epoch_fns.get(key)
        if fn is None:
            spec = self.packs[0].spec
            pe = P(EDGE_AXIS)

            def ep(flat, xb, yb):
                params = unravel_batched(spec, flat)
                params, loss = _cohort_epoch_body(
                    params, xb, yb, program, n_steps, lr, "gemm"
                )
                return ravel_batched(params), loss

            fn = jax.jit(
                jax.shard_map(ep, mesh=self.mesh, in_specs=(pe, pe, pe),
                              out_specs=(pe, pe))
            )
            self._epoch_fns[key] = fn
        return fn

    # -- layout -------------------------------------------------------------
    def _shard(self, arr, dtype) -> jnp.ndarray:
        return jax.device_put(jnp.asarray(np.asarray(arr, dtype)), self._edge_ns)

    def _layout(self, members: np.ndarray) -> _MeshLayout:
        members = np.asarray(members, np.int64)
        edge = self._client_edge[members]
        dev = edge // self._epe
        k = self.n_devices
        counts = np.bincount(dev, minlength=k)
        per = 1 << max(0, int(counts.max()) - 1).bit_length()  # pow2 row pad
        rows = k * per
        slot = np.empty(len(members), np.int64)
        offs = (np.arange(k) * per).copy()
        for c, d in enumerate(dev):  # members stay in order within a device
            slot[c] = offs[d]
            offs[d] += 1
        pad_members = np.full(rows, members[0] if len(members) else 0, np.int64)
        src = np.zeros(rows, np.int64)
        w = np.zeros(rows, np.float32)
        seg = np.repeat(np.arange(k, dtype=np.int64) * self._epe, per)
        pad_members[slot] = members
        src[slot] = np.arange(len(members))
        w[slot] = self._data_sizes[members]
        seg[slot] = edge
        return _MeshLayout(
            slot=slot, src=src, members=pad_members,
            seg=self._shard(seg, np.int32), w=self._shard(w, np.float32),
        )

    # -- run-loop seams -----------------------------------------------------
    def _broadcast_rows(self, global_rows, n: int):
        mat = jnp.broadcast_to(global_rows[0], (n, global_rows[0].shape[0]))
        return [jax.device_put(mat, self._edge_ns)]

    def _cloud_mean(self, edge_mat, weights):
        w = self._shard(weights, np.float32)
        self._cloud_syncs_done += 1
        return self._ledger.call("cloud_reduce", self._cloud_fn, edge_mat, w)

    def _edge_round_device(self, edge_mats):
        tel = self.tel
        m, n = self.assignment.shape
        with tel.span("assignment", round=self._round, engine="sync-mesh"):
            participating = self._draw_participation(m)
            active = self._has_edge & participating
            groups, passthrough = self._plan.draw(
                self.rng, active, self.schedule.local_steps
            )
            if tel.enabled:
                tel.metrics.set_gauge("participating", int(active.sum()))
        has = np.bincount(
            self._client_edge[np.nonzero(active)[0]], minlength=n
        ) > 0
        has_dev = self._shard(has, bool)
        single = len(groups) == 1 and not len(passthrough)
        loss_chunks: List[np.ndarray] = []
        num = den = None

        def accumulate(upd, lay):
            nonlocal num, den
            nm, dn = self._ledger.call(
                "edge_seg_sums", self._seg_sums_fn, upd, lay.seg, lay.w
            )
            num = nm if num is None else num + nm
            den = dn if den is None else den + dn

        for g in groups:
            lay = self._layout(g.members)
            with tel.span(
                "cohort_epoch", round=self._round, engine="sync-mesh",
                program=g.program.name, clients=len(g.members),
                epochs=int(g.idx.shape[1]), steps=g.steps, batch=g.batch,
            ):
                flat = self._ledger.call(
                    "edge_starts", self._starts_fn, edge_mats[0], lay.seg
                )
                pad_idx = g.idx[lay.src]  # (rows, epochs, steps, batch)
                ep_fn = self._epoch_fn(g.program, g.steps, g.lr)
                for e in range(g.idx.shape[1]):
                    xb, yb = self.store.gather(lay.members, pad_idx[:, e])
                    xb = jax.device_put(xb, self._edge_ns)
                    yb = jax.device_put(yb, self._edge_ns)
                    flat, loss = self._ledger.call("cohort_epoch", ep_fn, flat, xb, yb)
            loss_chunks.append(np.asarray(loss)[lay.slot])
            with tel.span(
                "edge_aggregate", round=self._round, engine="sync-mesh",
                clients=len(g.members), edges=n,
            ):
                if single:
                    edge_mats[0] = self._ledger.call(
                        "edge_agg", self._agg_keep_fn,
                        edge_mats[0], flat, lay.seg, lay.w, has_dev,
                    )
                else:
                    accumulate(flat, lay)
        if len(passthrough):  # empty shards upload their start row untouched
            lay = self._layout(passthrough)
            starts = self._ledger.call(
                "edge_starts", self._starts_fn, edge_mats[0], lay.seg
            )
            accumulate(starts, lay)
            loss_chunks.append(np.zeros(len(passthrough), np.float32))
        if not single and num is not None:
            edge_mats[0] = self._ledger.call(
                "edge_finish", self._finish_fn, num, den, has_dev, edge_mats[0]
            )
        self._edge_rounds_done += 1
        self._edge_account(participating, None)
        return edge_mats, loss_chunks

    # -- reporting ----------------------------------------------------------
    def comm_report(self) -> Dict[str, object]:
        """Measured HLO collective traffic next to the simulated ledger.

        ``cross_edge_bytes_per_cloud_round`` should be ~one model payload
        (the cloud psum) and the edge-round programs zero — the structural
        1/T claim asserted by ``tests/test_hfl_mesh.py`` and reported in
        ``BENCH_distributed.json``.
        """
        rep = self._ledger.report()
        d = int(self.pack.dim)
        rep.update(
            devices=self.n_devices,
            edges=int(self.assignment.shape[1]),
            edges_per_device=self._epe,
            payload_bytes=4 * d,
            edge_rounds=self._edge_rounds_done,
            cloud_syncs=self._cloud_syncs_done,
            cross_edge_bytes_per_cloud_round=(
                rep["cross_edge_total_bytes"] / max(1, self._cloud_syncs_done)
            ),
            cross_edge_bytes_per_edge_round=(
                rep["cross_edge_total_bytes"] / max(1, self._edge_rounds_done)
            ),
            simulated=self.accountant.totals(),
        )
        return rep


_SEG_MEAN_CACHE: Dict[tuple, object] = {}


def mesh_segment_mean(
    mesh: Mesh, updates, seg_ids, weights, n_segments: int
) -> np.ndarray:
    """Sharded per-segment weighted mean over an ``edge_mesh``: the mesh
    engine's edge-FedAvg kernel as a standalone oracle.

    Rows may arrive in any order and raggedly distributed across segments;
    they are grouped onto each segment's device block (padded per device
    with weight-0 rows) and averaged device-locally — the compiled program
    carries no cross-device collective.  Empty segments return zero rows,
    matching ``flat_segment_mean``.  Used by the hypothesis property test to
    pin mesh == ``flat_segment_mean`` == numpy on every harness mesh shape.
    """
    upd = np.asarray(updates, np.float32)
    seg = np.asarray(seg_ids, np.int64)
    w = np.asarray(weights, np.float32)
    k = _mesh_devices(mesh)
    if n_segments % k:
        raise ValueError(f"n_segments {n_segments} must divide by mesh size {k}")
    epe = n_segments // k
    dev = seg // epe
    counts = np.bincount(dev, minlength=k)
    per = 1 << max(0, int(counts.max()) - 1).bit_length()
    rows = k * per
    slot = np.empty(len(seg), np.int64)
    offs = (np.arange(k) * per).copy()
    for c, d in enumerate(dev):
        slot[c] = offs[d]
        offs[d] += 1
    pad_upd = np.zeros((rows, upd.shape[1]), np.float32)
    pad_w = np.zeros(rows, np.float32)
    pad_seg = np.repeat(np.arange(k, dtype=np.int64) * epe, per)
    pad_upd[slot] = upd
    pad_w[slot] = w
    pad_seg[slot] = seg

    key = (mesh, epe)
    fn = _SEG_MEAN_CACHE.get(key)
    if fn is None:
        pe = P(EDGE_AXIS)

        def _agg(u, s, ww):
            base = jax.lax.axis_index(EDGE_AXIS) * epe
            return hier_segment_aggregate_ref(u, s - base, ww, epe)

        fn = jax.jit(
            jax.shard_map(_agg, mesh=mesh, in_specs=(pe, pe, pe), out_specs=pe)
        )
        _SEG_MEAN_CACHE[key] = fn
    ns = NamedSharding(mesh, P(EDGE_AXIS))
    out = fn(
        jax.device_put(jnp.asarray(pad_upd), ns),
        jax.device_put(jnp.asarray(pad_seg.astype(np.int32)), ns),
        jax.device_put(jnp.asarray(pad_w), ns),
    )
    return np.asarray(out)

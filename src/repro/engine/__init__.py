"""Scalable batched/async HFL simulation engine.

A second simulation backend alongside ``federated.simulation.HFLSimulation``
(the readable reference), built for large client counts.  Every module is
model-agnostic: the workload is a ``ClientProgram``
(``federated.programs`` — CNN, MLP, transformer-LM, or anything registered
there), and the engines only ever touch it through its loss/init hooks and
flat parameter rows:

====================  =====================================================
module                role
====================  =====================================================
``flatten``           tree <-> (N, D) flat update matrices; ``flat_mean``
                      (one weighted average) and ``flat_segment_mean``
                      (every edge at once, (N, D) -> (E, D)) route FedAvg
                      through the Pallas kernels (``backend="pallas"``) or
                      plain-XLA contractions (``backend="reference"``)
``store``             ``DeviceShardStore`` — all client shards padded into
                      one (M, n_max, L, Ch) device array; cohort batches
                      gathered on device from int32 sample indices.
                      ``PagedShardStore`` — the streaming variant: a
                      bounded LRU working set paged from a lazy
                      ``ShardSource``, O(cohort) device memory
``cohort``            same-shape client cohorts trained by one
                      ``vmap(_local_epoch)`` call instead of M sequential
                      jitted calls; ``StreamCohortPlan`` derives the
                      grouping vectorized from an (M,) sizes array
``stream_sim``        ``StreamSyncEngine`` — population M as a streaming
                      axis: lazy shards, per-round cohort sampling
                      (``CohortSpec``), paged device store; memory and
                      round time scale with cohort size, not M
``events``            deterministic (time, seq) heap for discrete events
``sync_sim``          ``BatchedSyncEngine`` — reference semantics, batched
                      speed: a cloud round runs as a handful of
                      fixed-shape device programs (edge state as one
                      (E, D) matrix, segment-kernel aggregation)
``mesh_sim``          ``MeshSyncEngine`` — the device pipeline sharded over
                      a 1-D ``edge_mesh``: edges and their EUs' cohort rows
                      live on devices, edge FedAvg is device-local, and the
                      cloud reduce is the only cross-edge collective —
                      measured in compiled HLO by ``MeshCommLedger``
``async_sim``         ``AsyncHFLEngine`` — event-driven uploads, quorum
                      edge aggregation, staleness-decayed weighting; edge
                      models also live in one (E, D) matrix
``distill``           distillation aggregation for heterogeneous-MODEL
                      populations: per-architecture FedAvg stays flat, and
                      each edge's group models are fused by ensemble logit
                      distillation on a device-resident public shard
                      (``DistillSpec``, ``distill_fuse_flat``)
====================  =====================================================

Select via ``Scenario.simulate(..., engine="sync"|"async")``; mixed-model
populations come from ``build_scenario(model_mix={...})``.
"""
from repro.engine.async_sim import AsyncHFLEngine
from repro.engine.cohort import (
    LocalJob,
    StreamCohortPlan,
    draw_batch_indices,
    make_job,
    pack_for,
    run_cohorts,
)
from repro.engine.distill import (
    DistillSpec,
    distill_edge,
    distill_fuse_flat,
    draw_public_batches,
    kd_loss,
    soft_targets,
)
from repro.engine.events import Event, EventQueue
from repro.engine.flatten import BACKENDS, FlatPack, flat_mean, flat_segment_mean
from repro.engine.mesh_sim import MeshCommLedger, MeshSyncEngine, mesh_segment_mean
from repro.engine.store import DeviceShardStore, PagedShardStore
from repro.engine.stream_sim import StreamSyncEngine
from repro.engine.sync_sim import BatchedSyncEngine

__all__ = [
    "AsyncHFLEngine",
    "BACKENDS",
    "BatchedSyncEngine",
    "DeviceShardStore",
    "DistillSpec",
    "Event",
    "EventQueue",
    "FlatPack",
    "LocalJob",
    "MeshCommLedger",
    "MeshSyncEngine",
    "PagedShardStore",
    "StreamCohortPlan",
    "StreamSyncEngine",
    "distill_edge",
    "distill_fuse_flat",
    "draw_batch_indices",
    "draw_public_batches",
    "flat_mean",
    "flat_segment_mean",
    "kd_loss",
    "make_job",
    "mesh_segment_mean",
    "pack_for",
    "run_cohorts",
    "soft_targets",
]

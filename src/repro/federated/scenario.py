"""End-to-end experiment scenario builder: dataset -> EUs -> assignment -> sim.

Encapsulates the paper's two setups:
  * Heartbeat: 5 classes, 5 edges, 18 EUs (Table 3 edge distribution)
  * Seizure:   3 classes, 3 edges, 13 EUs (Table 2 edge distribution)
and exposes every assignment strategy for comparison.

``model=`` picks the client workload (``federated.programs`` registry):
  * ``"cnn"`` — the paper's 1-D CNN on the synthetic ECG/EEG shards
    (default; byte-identical to the pre-program builder);
  * ``"mlp"`` — a flattened-feature MLP classifier on the SAME shards, so
    every paper scenario doubles as an MLP workload;
  * ``"lm"`` / ``"moe"`` / ``"mamba"`` / ``"rwkv"`` — sequence LMs
    (dense transformer / mixture-of-experts / hybrid attn+Mamba / RWKV-6)
    on topic-skewed token-stream shards (``data.lm_stream``); sequence
    TOPICS play the role of classes, so the KLD-aware assignment still has
    imbalance to exploit.

``fedsgd=True`` wraps the chosen program in ``FedSGDProgram`` (one plain
SGD step per round, gradient uplink accounting); ``hparams=`` assigns
per-EU hyperparameter overrides (heterogeneous ``lr`` / ``batch_size`` /
``local_epochs`` / ``max_steps`` populations).

``model_mix=`` builds a heterogeneous-MODEL population: a mapping of
program names to EU counts (e.g. ``{"cnn": 12, "mlp": 6}``) assigns a
program per EU, generates one small PUBLIC shard per edge, and the
simulation engines fuse the per-architecture edge models by logit
distillation on that shard (``engine.distill``).
"""
from __future__ import annotations

import dataclasses
from typing import List, Mapping, Optional, Sequence

import jax
import numpy as np

from repro.core.assignment import AssignmentResult, dba_assignment, eara, random_assignment
from repro.core.hfl import HFLSchedule
from repro.data.lm_stream import TokenStream
from repro.data.partition import (
    TABLE2_SEIZURE,
    TABLE3_HEARTBEAT,
    eu_counts_from_edge_table,
    split_dataset_by_counts,
)
from repro.data.synthetic_health import Dataset, heartbeat_like, seizure_like
from repro.federated.client import FLClient
from repro.federated.programs import (
    PROGRAMS,
    SEQUENCE_PROGRAMS,
    ClientProgram,
    CNNProgram,
    FedSGDProgram,
    MLPProgram,
)
from repro.federated.simulation import (
    HeteroHFLSimulation,
    HFLSimulation,
    SimResult,
    centralized_baseline,
)
from repro.models.cnn1d import HEARTBEAT_CNN, SEIZURE_CNN
from repro.utils.tree import tree_size_bytes
from repro.wireless.channel import WirelessParams, build_cost_matrices, sample_topology


@dataclasses.dataclass
class Scenario:
    name: str
    program: ClientProgram
    clients: List[FLClient]
    test: Dataset
    class_counts: np.ndarray  # (M, K)
    topo: object
    cost: object
    wp: WirelessParams
    model_bits: float
    init_edge: np.ndarray
    # heterogeneous-model federation (model_mix= scenarios): one public
    # Dataset per edge for the distillation fuse, plus the fuse's default
    # knobs; both None for homogeneous populations
    public: Optional[List[Dataset]] = None
    distill: object = None
    # default fault model (repro.faults.FaultSpec); None = fault-free.  A
    # fresh FaultState is built per simulate() call so runs never share
    # energy balances or dispatch counters
    faults: object = None

    @property
    def is_hetero(self) -> bool:
        """True when the population mixes client programs (architectures)."""
        return len({c.program for c in self.clients}) > 1

    @property
    def cfg(self):
        """Legacy alias: the bare ``CNNConfig`` for CNN scenarios (pre-PR 3
        call sites passed that into engines; ``as_program`` coerces it
        back), otherwise the program itself — an LM's inner ``ModelConfig``
        would NOT coerce, so non-CNN scenarios must hand engines a real
        program."""
        return self.program.cfg if isinstance(self.program, CNNProgram) else self.program

    @property
    def n_edges(self) -> int:
        return self.cost.latency.shape[1]

    def assign(self, strategy: str, **kw) -> AssignmentResult:
        if strategy == "dba":
            return dba_assignment(self.class_counts, self.topo.dist)
        if strategy == "random":
            return random_assignment(self.class_counts, self.n_edges, **kw)
        if strategy in ("eara-sca", "eara-dca", "eara-sca+", "eara-dca+"):
            mode = "sca" if "sca" in strategy else "dca"
            return eara(
                self.class_counts,
                self.cost,
                self.wp,
                self.model_bits,
                self.topo.tx_power_max,
                mode=mode,
                refine=strategy.endswith("+"),
                **kw,
            )
        raise ValueError(strategy)

    def simulate(
        self,
        assignment: np.ndarray,
        cloud_rounds: int,
        schedule: HFLSchedule = HFLSchedule(1, 1),
        seed: int = 0,
        upp: float = 1.0,
        track_divergence: bool = False,
        eval_every: int = 1,
        wall_clock: bool = False,
        engine: str = "reference",
        backend: str = "pallas",
        compression=None,
        staleness_decay: float = 0.5,
        quorum: float = 0.75,
        distill=None,
        faults=None,
        telemetry=None,
        cohort=None,
        server_momentum: float = 0.0,
        mesh=None,
        serve=None,
    ) -> SimResult:
        """Run the scenario through one of the simulation engines.

        engine:   "reference" — the sequential readable simulator;
                  "sync"      — batched cohorts + flat-buffer aggregation,
                                same semantics as the reference;
                  "async"     — event-driven staleness-weighted engine
                                (extra knobs: ``staleness_decay`` in
                                [0, 1], ``quorum`` in (0, 1]).
        backend:  aggregation path for the engines ("pallas" | "reference").
        mesh:     None | device count | ``jax.sharding.Mesh`` with an
                  ``"edge"`` axis — with ``engine="sync"``, runs the sync
                  engine's round sharded over that ``edge_mesh``
                  (``MeshSyncEngine``); the edge count must divide by the
                  mesh size.  The returned ``SimResult`` then carries the
                  engine's HLO collective accounting as ``.comm_report``.
        compression: None | ``core.compression.CompressionSpec`` (kinds
                  "topk" | "ternary" | "none") applied to uplinks with
                  error feedback; the accountant then counts compressed
                  bits.  Overrides any program-level uplink quantization
                  (FedSGD ``grad_bits=16``).
        upp:      per-round client participation probability in (0, 1].
        distill:  ``engine.distill.DistillSpec`` override for the
                  heterogeneous-model fuse; None uses the scenario's
                  default (``model_mix=`` scenarios carry one).  Ignored
                  for homogeneous populations.
        faults:   ``repro.faults.FaultSpec`` override for the fault layer
                  (client churn, energy budgets, time-varying channels,
                  retry/timeout policy); ``None`` uses the scenario's
                  default (``build_scenario(faults=...)``), ``False``
                  forces the fault-free path.  A fresh ``FaultState`` is
                  built per call — runs never share energy balances.
        telemetry: the observability knob (``docs/OBSERVABILITY.md``).
                  ``None``/``False`` — off, zero overhead; ``True`` — record
                  in memory (``SimResult.telemetry``); a path — record AND
                  flush trace/rounds/metrics artifacts there after the run;
                  a ``repro.telemetry.Telemetry`` — record into it.
        cohort:   None (full participation / UPP) or a
                  ``repro.federated.sampling.CohortSpec`` — every engine
                  then trains only the spec's per-round cohort, drawn from
                  a keyed side-channel generator (requires ``upp=1.0``).
        server_momentum: cloud-side momentum coefficient on the aggregated
                  model delta (0.0 = plain FedAvg, the pinned default).
        serve:    None (training only) or a
                  ``repro.serving.TrafficSpec`` — the engines then hot-swap
                  each cloud round's global model behind a deterministic
                  query stream drawn from the scenario's own shards and
                  report ``serve_qps`` / ``serve_staleness_rounds`` /
                  ``serve_acc`` per round (``SimResult.serve_history`` and,
                  under telemetry, ``rounds.jsonl`` + metric gauges).
                  Traffic draws come from a keyed side-channel generator,
                  so training trajectories are bit-identical serve-on vs
                  serve-off.  Homogeneous populations only.
        """
        from repro.telemetry import coerce_telemetry

        distill = distill if distill is not None else self.distill
        if self.is_hetero and (cohort is not None or server_momentum):
            raise ValueError(
                "cohort sampling / server momentum are not supported for "
                "heterogeneous-model populations"
            )
        spec = self.faults if faults is None else (faults or None)
        fault_state = None
        if spec is not None:
            from repro.faults import FaultSpec, FaultState

            if not isinstance(spec, FaultSpec):
                raise TypeError(
                    f"faults must be a repro.faults.FaultSpec, got {type(spec).__name__}"
                )
            fault_state = FaultState(
                spec, self.topo, self.wp, self.model_bits,
                class_counts=self.class_counts,
            )
        tel = coerce_telemetry(telemetry)
        serve_state = None
        if serve is not None:
            from repro.serving.traffic import ServeTraffic, TrafficSpec

            if not isinstance(serve, TrafficSpec):
                raise TypeError(
                    f"serve must be a repro.serving.TrafficSpec, got "
                    f"{type(serve).__name__}"
                )
            if self.is_hetero:
                raise ValueError(
                    "serve traffic targets THE global model; "
                    "heterogeneous-model populations have one per group"
                )
            serve_state = ServeTraffic(serve, self.clients, self.program, tel)
        try:
            return self._simulate(
                assignment, cloud_rounds, schedule, seed, upp, track_divergence,
                eval_every, wall_clock, engine, backend, compression,
                staleness_decay, quorum, distill, fault_state, tel,
                cohort, server_momentum, mesh, serve_state,
            )
        finally:
            if tel is not None and tel.out_dir is not None:
                tel.flush()

    def _simulate(
        self,
        assignment,
        cloud_rounds,
        schedule,
        seed,
        upp,
        track_divergence,
        eval_every,
        wall_clock,
        engine,
        backend,
        compression,
        staleness_decay,
        quorum,
        distill,
        faults,
        telemetry,
        cohort=None,
        server_momentum=0.0,
        mesh=None,
        serve=None,
    ) -> SimResult:
        if engine == "reference":
            if self.is_hetero:
                if track_divergence or wall_clock:
                    raise ValueError(
                        "track_divergence/wall_clock are not defined for "
                        "heterogeneous-model populations"
                    )
                if faults is not None:
                    raise ValueError(
                        "the hetero reference simulator does not support "
                        "fault injection; use engine='sync' or 'async' for "
                        "heterogeneous-model populations under faults"
                    )
                sim = HeteroHFLSimulation(
                    self.clients,
                    assignment,
                    self.test,
                    schedule=schedule,
                    seed=seed,
                    upp=upp,
                    public=self.public,
                    distill=distill,
                    compression=compression,
                    telemetry=telemetry,
                )
                return sim.run(cloud_rounds, eval_every=eval_every)
            sim = HFLSimulation(
                self.clients,
                assignment,
                self.program,
                self.test,
                schedule=schedule,
                seed=seed,
                upp=upp,
                track_divergence=track_divergence,
                cost_latency=self.cost.latency if wall_clock else None,
                compression=compression,
                faults=faults,
                telemetry=telemetry,
                cohort=cohort,
                server_momentum=server_momentum,
                serve=serve,
            )
            res = sim.run(cloud_rounds, eval_every=eval_every)
            if wall_clock:
                res.wall_seconds = sim.clock.seconds
            return res
        if engine == "sync" and mesh is not None:
            from repro.engine import MeshSyncEngine

            sim = MeshSyncEngine(
                self.clients,
                assignment,
                self.program,
                self.test,
                schedule=schedule,
                seed=seed,
                upp=upp,
                track_divergence=track_divergence,
                cost_latency=self.cost.latency if wall_clock else None,
                backend=backend,
                compression=compression,
                faults=faults,
                telemetry=telemetry,
                cohort=cohort,
                server_momentum=server_momentum,
                mesh=mesh,
                serve=serve,
            )
            res = sim.run(cloud_rounds, eval_every=eval_every)
            res.comm_report = sim.comm_report()
            return res
        if engine == "sync":
            from repro.engine import BatchedSyncEngine

            sim = BatchedSyncEngine(
                self.clients,
                assignment,
                self.program,
                self.test,
                schedule=schedule,
                seed=seed,
                upp=upp,
                track_divergence=track_divergence,
                cost_latency=self.cost.latency if wall_clock else None,
                backend=backend,
                compression=compression,
                public_shards=self.public,
                distill=distill,
                faults=faults,
                telemetry=telemetry,
                cohort=cohort,
                server_momentum=server_momentum,
                serve=serve,
            )
            return sim.run(cloud_rounds, eval_every=eval_every)
        if engine == "async":
            from repro.engine import AsyncHFLEngine

            if track_divergence:
                raise ValueError(
                    "engine='async' does not support track_divergence; "
                    "use engine='reference' or 'sync'"
                )
            sim = AsyncHFLEngine(
                self.clients,
                assignment,
                self.program,
                self.test,
                latency=self.cost.latency,
                schedule=schedule,
                seed=seed,
                upp=upp,
                staleness_decay=staleness_decay,
                quorum=quorum,
                backend=backend,
                compression=compression,
                public_shards=self.public,
                distill=distill,
                faults=faults,
                telemetry=telemetry,
                cohort=cohort,
                server_momentum=server_momentum,
                serve=serve,
            )
            return sim.run(cloud_rounds, eval_every=eval_every)
        raise ValueError(f"unknown engine {engine!r} (reference | sync | async)")

    def centralized(self, rounds: int, seed: int = 0, eval_every: int = 1):
        batch = 10 * self.n_edges  # paper: local batch x n_edges (50 / 30)
        return centralized_baseline(
            self.clients, self.program, self.test, rounds, batch=batch, seed=seed,
            eval_every=eval_every,
        )


def _eus_per_edge(n_edges: int, n_eus: int) -> List[int]:
    base = n_eus // n_edges
    extra = n_eus - base * n_edges
    return [base + (1 if j < extra else 0) for j in range(n_edges)]


def _hparam_kwargs(
    hparams: Optional[Sequence[Optional[Mapping]]], n_eus: int
) -> List[dict]:
    """Validate per-EU hyperparameter overrides into FLClient kwargs.

    Overrides are passed to the ``FLClient`` CONSTRUCTOR (not set after the
    fact), so ``__post_init__`` validation applies to them too.
    """
    if hparams is None:
        return [{}] * n_eus
    if len(hparams) != n_eus:
        raise ValueError(
            f"hparams must have one entry per EU ({n_eus}), got {len(hparams)}"
        )
    allowed = {"lr", "batch_size", "local_epochs", "max_steps"}
    out = []
    for hp in hparams:
        hp = dict(hp or {})
        unknown = set(hp) - allowed
        if unknown:
            raise ValueError(
                f"unknown hyperparameters {sorted(unknown)}; allowed: {sorted(allowed)}"
            )
        out.append(hp)
    return out


def _mix_programs(
    model_mix: Mapping[str, int], n_eus: int, allowed: Sequence[str], make
) -> tuple:
    """Validate a ``model_mix`` mapping into per-EU programs.

    ``model_mix`` maps program names to EU counts (summing to the
    population size); EUs take programs in mapping order — the first
    ``model_mix[a]`` EUs run ``a``, the next block ``b``, and so on, so the
    capability skew lands on a deterministic slice of the population and
    EARA's KLD assignment interacts with it reproducibly.  ``make`` builds
    the program for one name.
    """
    if not model_mix:
        raise ValueError("model_mix must name at least one program")
    unknown = set(model_mix) - set(allowed)
    if unknown:
        raise ValueError(
            f"model_mix programs {sorted(unknown)} not supported here; "
            f"allowed: {sorted(allowed)}"
        )
    counts = {name: int(c) for name, c in model_mix.items()}
    if any(c < 1 for c in counts.values()):
        raise ValueError(f"model_mix counts must be >= 1, got {model_mix}")
    if sum(counts.values()) != n_eus:
        raise ValueError(
            f"model_mix counts must sum to the population size {n_eus}, "
            f"got {sum(counts.values())}"
        )
    programs = {name: make(name) for name in counts}
    per_eu: List[ClientProgram] = []
    for name, c in counts.items():
        per_eu += [programs[name]] * c
    return per_eu, list(programs.values())


def build_scenario(
    dataset: str = "heartbeat",
    *,
    model: str = "cnn",
    model_mix: Optional[Mapping[str, int]] = None,
    public_per_edge: int = 16,
    fedsgd: bool = False,
    grad_bits: int = 32,
    hparams: Optional[Sequence[Optional[Mapping]]] = None,
    faults=None,
    seed: int = 0,
    scale: float = 1.0,
    mean_dist: float = 300.0,
    n_test_per_class: int = 300,
    wp: Optional[WirelessParams] = None,
    lm_eus: int = 12,
    lm_edges: int = 4,
    lm_topics: int = 4,
    lm_seq_len: int = 32,
    lm_vocab: int = 128,
    lazy: bool = False,
    n_eus: Optional[int] = None,
    n_edges: Optional[int] = None,
) -> Scenario:
    """Construct an experimental setup with synthetic data.

    ``dataset`` picks the shards ("heartbeat" | "seizure" | "lm"), ``model``
    the client program:

      * ``"cnn"`` | ``"mlp"`` — classifiers on the synthetic health shards;
      * ``"lm"`` | ``"moe"`` | ``"mamba"`` | ``"rwkv"`` — sequence LMs on
        the topic-skewed token-stream population (``dataset="lm"`` implied;
        conversely ``dataset="lm"`` defaults the model to ``"lm"``).

    ``model_mix`` (optional, instead of ``model``) builds a
    heterogeneous-MODEL population: a mapping of program names to EU
    counts summing to the population size, e.g. ``{"cnn": 12, "mlp": 6}``
    on the health shards or ``{"lm": 8, "moe": 4}`` on the token streams
    (families cannot cross: the architectures under one edge must share a
    shard layout and logit alphabet for the distillation fuse).  The
    scenario then carries one small PUBLIC shard per edge
    (``public_per_edge`` samples each) and a default
    ``engine.distill.DistillSpec``; the engines fuse the per-architecture
    edge models on it once per cloud round.

    ``fedsgd=True`` wraps the chosen program in ``FedSGDProgram`` — one
    plain-SGD step per round and gradient-payload uplink accounting
    (``grad_bits`` = 32 exact | 16 fp16-cast gradients).

    ``hparams`` (optional) is one mapping per EU (or None entries) of
    ``FLClient`` overrides — ``lr`` | ``batch_size`` | ``local_epochs`` |
    ``max_steps`` — building heterogeneous-hyperparameter populations; the
    engines cohort clients by the resulting tuples.

    ``faults`` (optional) is a ``repro.faults.FaultSpec`` the scenario
    carries as its default fault model: every ``simulate()`` call then
    runs under client churn / energy budgets / time-varying channels
    unless overridden (``simulate(faults=False)`` forces fault-free).

    The ``lm_*`` knobs size the sequence-model population; ``scale``
    scales sequences-per-EU there just as it scales samples in the health
    setups.
    """
    if lazy:
        # streaming mode: a ShardSource population with analytic (no-data)
        # class histograms and a compact striped assignment.  A NEW
        # population family — eager scenarios (and their golden pins) are
        # untouched; the lazy guarantee is shard(cid) purity in (seed, cid).
        if model_mix is not None or hparams is not None or faults is not None:
            raise ValueError(
                "lazy mode supports homogeneous fault-free populations "
                "(model_mix/hparams/faults are per-client state, O(M))"
            )
        if n_eus is None:
            raise ValueError("lazy mode requires n_eus= (population size)")
        from repro.federated.stream import build_stream_scenario

        return build_stream_scenario(
            dataset,
            n_eus=n_eus,
            n_edges=n_edges if n_edges is not None else 8,
            model=model,
            fedsgd=fedsgd,
            grad_bits=grad_bits,
            seed=seed,
            n_test_per_class=n_test_per_class,
            lm_topics=lm_topics,
            lm_seq_len=lm_seq_len,
            lm_vocab=lm_vocab,
        )
    if n_eus is not None or n_edges is not None:
        raise ValueError("n_eus/n_edges are lazy-mode knobs (pass lazy=True)")
    if model_mix is not None and fedsgd:
        raise ValueError("model_mix and fedsgd cannot combine (pick one)")
    if model_mix is not None and model != "cnn":  # "cnn" is the unset default
        raise ValueError(
            f"pass either model= or model_mix=, not both (got model={model!r})"
        )
    seq_model = model in SEQUENCE_PROGRAMS
    seq_mix = model_mix is not None and set(model_mix) <= set(SEQUENCE_PROGRAMS)
    if model_mix is not None and not seq_mix:
        bad = set(model_mix) & set(SEQUENCE_PROGRAMS)
        if bad:
            raise ValueError(
                "model_mix cannot cross families: sequence programs "
                f"{sorted(bad)} do not share a shard layout with {sorted(set(model_mix) - bad)}"
            )
        if dataset == "lm":
            raise ValueError(
                f"dataset='lm' requires a sequence model_mix {SEQUENCE_PROGRAMS}, "
                f"got {sorted(model_mix)}"
            )
    if dataset == "lm" or seq_model or seq_mix:
        if not (seq_model or seq_mix) and model != "cnn":  # "cnn" is the unset default
            raise ValueError(
                f"dataset='lm' requires a sequence model {SEQUENCE_PROGRAMS}, got {model!r}"
            )
        return _build_lm_scenario(
            model=model if seq_model else "lm",
            model_mix=model_mix if seq_mix else None,
            public_per_edge=public_per_edge,
            fedsgd=fedsgd,
            grad_bits=grad_bits,
            hparams=hparams,
            faults=faults,
            seed=seed,
            scale=scale,
            mean_dist=mean_dist,
            n_test_per_class=n_test_per_class,
            wp=wp,
            n_eus=lm_eus,
            n_edges=lm_edges,
            n_topics=lm_topics,
            seq_len=lm_seq_len,
            vocab=lm_vocab,
        )
    rng = np.random.default_rng(seed)
    if dataset == "heartbeat":
        table, n_eus, cnn = TABLE3_HEARTBEAT, 18, HEARTBEAT_CNN
        maker = heartbeat_like
    elif dataset == "seizure":
        table, n_eus, cnn = TABLE2_SEIZURE, 13, SEIZURE_CNN
        maker = seizure_like
    else:
        raise ValueError(dataset)
    n_edges, k = table.shape
    counts, init_edge = eu_counts_from_edge_table(
        rng, table, _eus_per_edge(n_edges, n_eus), scale=scale
    )
    train = maker(rng, counts.sum(axis=0))
    shards = split_dataset_by_counts(rng, train, counts)
    test = maker(rng, np.full(k, n_test_per_class))

    def make_health(name: str) -> ClientProgram:
        if name == "cnn":
            return CNNProgram(cnn)
        if name == "mlp":
            return MLPProgram(feat=(cnn.seq_len, cnn.in_channels), classes=k)
        raise ValueError(
            f"unknown model {name!r} (cnn | mlp | {' | '.join(SEQUENCE_PROGRAMS)})"
        )

    public = None
    distill = None
    if model_mix is not None:
        per_eu, distinct = _mix_programs(model_mix, n_eus, ("cnn", "mlp"), make_health)
        program = per_eu[0]
        if len(distinct) > 1:
            # one small public pool per edge, drawn AFTER the private shards
            # so the population above is byte-identical to the homogeneous
            # builder at equal seeds
            per_class = np.full(k, max(1, public_per_edge // k))
            public = [maker(rng, per_class) for _ in range(n_edges)]
            from repro.engine.distill import DistillSpec

            distill = DistillSpec()
    else:
        program = make_health(model)
        if fedsgd:
            program = FedSGDProgram(base=program, grad_bits=grad_bits)
        per_eu = [program] * n_eus
    kw = _hparam_kwargs(hparams, n_eus)
    clients = [FLClient(i, shards[i], per_eu[i], **kw[i]) for i in range(n_eus)]
    wp = wp or WirelessParams()
    topo = sample_topology(
        jax.random.PRNGKey(seed), n_eus, n_edges, mean_dist=mean_dist,
        dataset_sizes=counts.sum(axis=1),
    )
    # mixed fleets size the airtime estimate by the LARGEST architecture —
    # the conservative payload for EARA's energy/latency costs
    model_bits = max(
        tree_size_bytes(p.init(jax.random.PRNGKey(0))) * 8
        for p in {c.program for c in clients}
    )
    cost = build_cost_matrices(topo, model_bits, wp)
    if model_mix is not None and len({c.program for c in clients}) > 1:
        name = f"{dataset}-mix(" + "+".join(model_mix) + ")"
    elif program.name == "cnn":
        name = f"{dataset}"
    else:
        name = f"{dataset}-{program.name}"
    return Scenario(
        name=name,
        program=program,
        clients=clients,
        test=test,
        class_counts=counts,
        topo=topo,
        cost=cost,
        wp=wp,
        model_bits=model_bits,
        init_edge=init_edge,
        public=public,
        distill=distill,
        faults=faults,
    )


def _build_lm_scenario(
    *,
    model: str,
    model_mix: Optional[Mapping[str, int]] = None,
    public_per_edge: int = 16,
    fedsgd: bool,
    grad_bits: int,
    hparams: Optional[Sequence[Optional[Mapping]]],
    faults=None,
    seed: int,
    scale: float,
    mean_dist: float,
    n_test_per_class: int,
    wp: Optional[WirelessParams],
    n_eus: int,
    n_edges: int,
    n_topics: int,
    seq_len: int,
    vocab: int,
) -> Scenario:
    """Topic-skewed token-stream population for the sequence programs
    (dense LM / MoE / Mamba / RWKV — ``model`` picks which).

    Each EU's shard is dominated by one Markov TOPIC (the ``lm_stream``
    transition-matrix families) with a sprinkle of the others — the LM
    counterpart of the paper's per-EU dominant-class imbalance, recorded in
    ``class_counts`` so EARA balances edge TOPIC mixtures exactly as it
    balances edge class mixtures in the health setups.  The shard layout is
    identical for every sequence program ((N, seq_len) int32), so the SAME
    population compares workloads apples-to-apples.
    """
    rng = np.random.default_rng(seed)
    base = max(1, int(round(40 * scale)))
    # dominant topic gets ~8x the sideline topics' sequence counts
    counts = rng.integers(0, base + 1, (n_eus, n_topics)).astype(np.int64)
    dom = rng.integers(0, n_topics, n_eus)
    counts[np.arange(n_eus), dom] += 8 * base
    streams = [TokenStream(vocab, seed=seed, topic=t) for t in range(n_topics)]
    shards = []
    for i in range(n_eus):
        xs, ys = [], []
        for t in range(n_topics):
            c = int(counts[i, t])
            if c == 0:
                continue
            xs.append(streams[t].batch(c, seq_len))
            ys.append(np.full((c,), t, np.int32))
        x = np.concatenate(xs, 0)
        y = np.concatenate(ys, 0)
        perm = rng.permutation(len(y))
        shards.append(Dataset(x[perm], y[perm], n_classes=n_topics))
    # fresh streams for the test set so it never replays training state
    test_streams = [
        TokenStream(vocab, seed=seed + 7919, topic=t) for t in range(n_topics)
    ]
    test = Dataset(
        np.concatenate([s.batch(n_test_per_class, seq_len) for s in test_streams], 0),
        np.concatenate(
            [np.full((n_test_per_class,), t, np.int32) for t in range(n_topics)], 0
        ),
        n_classes=n_topics,
    )
    # the registry factories build the tiny IoT-sized config per model, so
    # a newly registered sequence program is reachable here for free
    def make_seq(name: str) -> ClientProgram:
        return PROGRAMS.get(name)(vocab_size=vocab, seq_len=seq_len, n_topics=n_topics)

    public = None
    distill = None
    if model_mix is not None:
        per_eu, distinct = _mix_programs(model_mix, n_eus, SEQUENCE_PROGRAMS, make_seq)
        program = per_eu[0]
        if len(distinct) > 1:
            # per-edge public token pools from fresh streams (never replay
            # training or test state); drawn after everything else so the
            # population matches the homogeneous builder at equal seeds
            pub_streams = [
                TokenStream(vocab, seed=seed + 3571, topic=t) for t in range(n_topics)
            ]
            per_topic = max(1, public_per_edge // n_topics)
            public = []
            for _ in range(n_edges):
                px = np.concatenate(
                    [s.batch(per_topic, seq_len) for s in pub_streams], 0
                )
                py = np.concatenate(
                    [np.full((per_topic,), t, np.int32) for t in range(n_topics)], 0
                )
                public.append(Dataset(px, py, n_classes=n_topics))
            from repro.engine.distill import DistillSpec

            distill = DistillSpec()
    else:
        program = make_seq(model)
        if fedsgd:
            program = FedSGDProgram(base=program, grad_bits=grad_bits)
        per_eu = [program] * n_eus
    kw = _hparam_kwargs(hparams, n_eus)
    clients = [FLClient(i, shards[i], per_eu[i], **kw[i]) for i in range(n_eus)]
    wp = wp or WirelessParams()
    topo = sample_topology(
        jax.random.PRNGKey(seed), n_eus, n_edges, mean_dist=mean_dist,
        dataset_sizes=counts.sum(axis=1),
    )
    model_bits = max(
        tree_size_bytes(p.init(jax.random.PRNGKey(0))) * 8
        for p in {c.program for c in clients}
    )
    cost = build_cost_matrices(topo, model_bits, wp)
    name = (
        "mix(" + "+".join(model_mix) + ")"
        if model_mix is not None and len({c.program for c in clients}) > 1
        else program.name
    )
    return Scenario(
        name=name,
        program=program,
        clients=clients,
        test=test,
        class_counts=counts,
        topo=topo,
        cost=cost,
        wp=wp,
        model_bits=model_bits,
        # no Table-2/3 edge pools here; the "initial edge" is each EU's
        # nearest edge (a valid edge INDEX, unlike the dominant-topic id)
        init_edge=np.asarray(topo.dist).argmin(axis=1),
        public=public,
        distill=distill,
        faults=faults,
    )

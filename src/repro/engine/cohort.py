"""Cohort-batched local training: one jitted call per same-shape client group.

The reference simulator trains M clients with M sequential jitted calls; at
M=512 the per-call dispatch and per-client conversions dominate wall clock.
Here clients whose padded shard shape agrees — same steps bucket, local
epoch count, batch size, and learning rate — are stacked into a leading
*cohort* axis and trained by ONE jitted vmapped-gradient call per step, so
per-client heterogeneous hyperparameters cost one cohort per distinct
tuple rather than a recompile per client.

Batch-index sampling intentionally replicates ``FLClient.local_update``
draw-for-draw (permutation, then resample-padding) so that the sync engine
consumes the numpy RNG stream in exactly the reference order — that is what
makes fixed-seed sync runs reproduce the reference accuracy trajectory.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.flatten import FlatPack, ravel_batched, unravel_batched
from repro.federated.client import FLClient
from repro.federated.programs import ClientProgram, group_clients
from repro.telemetry import NULL_TELEMETRY, register_jit
from repro.utils.tree import tree_size_bytes

# FlatPack is architecture-determined (the spec depends only on the program,
# not on parameter values), so one cached pack per program serves every
# caller that meets a program through a client rather than a constructor arg
# (mixed-program cohorts in run_cohorts, the hetero engines' group packs).
_PACKS: Dict[ClientProgram, FlatPack] = {}


def pack_for(program: ClientProgram) -> FlatPack:
    pack = _PACKS.get(program)
    if pack is None:
        pack = FlatPack(program.init(jax.random.PRNGKey(0)))
        _PACKS[program] = pack
    return pack


@dataclasses.dataclass
class GroupState:
    """Per-architecture-group engine state (heterogeneous-model federation).

    One entry per distinct client program, in first-appearance order:
    parameter trees, flat packs, model bits, and the per-EU uplink payload
    (an explicit ``CompressionSpec`` priced on each group's own flat
    layout, else the program's ``uplink_bits``).  Built identically by the
    sync and async engines through :func:`build_group_state`, so the two
    cannot drift apart.
    """

    programs: List[ClientProgram]
    group_of: np.ndarray  # (M,) client -> group index
    params: List  # per-group parameter trees
    packs: List[FlatPack]
    bits: List[float]  # per-group model bits
    uplink_bits: List[float]  # per-group EU->edge upload payload


def build_group_state(
    clients, program: ClientProgram, params, pack: FlatPack, seed: int, compression=None
) -> GroupState:
    """Partition ``clients`` by program and build each group's state.

    ``program``/``params``/``pack`` are the engine's primary objects — the
    primary group REUSES them (that identity is what keeps homogeneous
    runs bit-identical to the single-group engines); other groups init
    from the same seed.  A constructor program no client trains is
    refused: the accounting defaults (downlink/cloud payloads) would
    silently follow an unused model.
    """
    programs, group_of = group_clients(clients, fallback=program)
    if clients and program not in programs:
        raise ValueError(
            f"engine program {program.name!r} matches none of the clients' "
            f"programs {[p.name for p in programs]}"
        )
    group_params = [
        params if p == program else p.init(jax.random.PRNGKey(seed))
        for p in programs
    ]
    packs = [
        pack if p == program else FlatPack(t)
        for p, t in zip(programs, group_params)
    ]
    bits = [tree_size_bytes(t) * 8 for t in group_params]
    if compression is not None and compression.kind != "none":
        # bits() on the flat (D_g,) layout the engines actually compress
        # (one global top-k), not the per-leaf tree the reference uses
        uplink = [
            compression.bits(jnp.zeros((pk.dim,), jnp.float32)) for pk in packs
        ]
    else:
        # program-level uplink semantics (FedSGD gradient payloads)
        uplink = [p.uplink_bits(b) for p, b in zip(programs, bits)]
    return GroupState(programs, group_of, group_params, packs, bits, uplink)


@dataclasses.dataclass
class LocalJob:
    """One client's pending local-training work for a round.

    Start parameters travel as a flat (D,) row (``engine.flatten`` layout):
    per-client pytree conversions are the dominant overhead at M >= 512, so
    the engines stay flat-major and cohorts convert once per batch.
    """

    client: FLClient
    start_flat: "jnp.ndarray"  # (D,)
    idx: List[np.ndarray]  # per-epoch (steps, batch) sample indices
    steps: int
    tag: object = None  # CohortResult key; defaults to client.cid

    def __post_init__(self):
        if self.tag is None:
            self.tag = self.client.cid

    @property
    def key(self) -> Tuple:
        """Cohort grouping key: clients stack into one vmapped call only when
        their PROGRAM, padded step count, epoch count, batch size, AND
        learning rate agree — program identity leads the tuple because a
        heterogeneous-model population must never stack two architectures'
        (C, D) rows, and within one architecture heterogeneous
        hyperparameters still split into one fixed-shape cohort each."""
        return (
            self.client.program,
            self.steps,
            len(self.idx),
            self.client.batch_size,
            self.client.lr,
        )


def draw_batch_indices(
    rng: np.random.Generator, n: int, steps: int, batch: int, epochs: int
) -> List[np.ndarray]:
    """Replicates FLClient.local_update's sampling, one draw pair per epoch."""
    out = []
    for _ in range(epochs):
        idx = rng.permutation(n)
        need = steps * batch
        if need > n:  # pad by resampling
            idx = np.concatenate([idx, rng.integers(0, n, need - n)])
        out.append(idx[:need].reshape(steps, batch))
    return out


def make_job(
    client: FLClient, start_flat, rng: np.random.Generator, epochs: int, tag=None
) -> LocalJob:
    """Build one client's round job.  ``epochs`` is the schedule default;
    the client's own ``local_epochs`` / the program's ``single_step`` clamp
    override it (same resolution as ``FLClient.local_update``)."""
    n = len(client.shard)
    if n == 0:
        return LocalJob(client, start_flat, [], 0, tag=tag)
    steps = client.plan_steps()
    epochs = client.epochs_for(epochs)
    return LocalJob(
        client, start_flat, draw_batch_indices(rng, n, steps, client.batch_size, epochs),
        steps, tag=tag,
    )


def _cohort_epoch_body(
    params, xb, yb, program: ClientProgram, n_steps: int, lr: float, impl: str
):
    """params: pytree with leading cohort axis C; xb: (C, n_steps, B, *feat).

    Equivalent to ``vmap(_local_epoch)`` but with the steps-scan OUTSIDE the
    vmap: only the per-step gradient is vmapped, while the Adam update runs
    directly on the stacked (C, ...) trees.  Adam is purely elementwise, so
    the per-client arithmetic is bit-identical to ``_local_epoch``; hoisting
    the scan avoids shuffling the (C, D)-sized optimizer carry through a
    vmapped scan, which dominates wall clock at large C.

    ``program`` supplies the per-example loss AND the local optimizer
    (``make_optimizer``: Adam for the FedAvg programs, plain SGD for the
    FedSGD wrapper — the optimizer update is elementwise either way, so the
    per-client arithmetic stays bit-identical to ``_local_epoch``);
    ``impl`` threads the
    formulation knob through (for the CNN: "gemm" — the engines' default —
    lowers the vmapped per-client convolutions to batched GEMMs instead of
    the C-group convolution XLA:CPU serializes; "xla" is the plain
    convolution form.  Single-formulation programs ignore it.)
    """
    opt = program.make_optimizer(lr)
    opt_state = opt.init(params)

    def client_loss(p, x, y):
        return program.loss(p, x, y, impl=impl)

    grad_fn = jax.vmap(jax.value_and_grad(client_loss))

    def body(carry, batch):
        params, opt_state, step = carry
        x, y = batch  # (C, B, L, Ch), (C, B)
        loss, grads = grad_fn(params, x, y)
        params, opt_state = opt.update(params, grads, opt_state, step)
        return (params, opt_state, step + 1), loss

    carry = (params, opt_state, jnp.zeros((), jnp.int32))
    if n_steps <= 16:
        # full unroll: XLA's while loop double-buffers the (C, D)-sized
        # params+Adam carry every iteration on CPU, which costs more than the
        # gradient itself at large C; short step counts (the large-M regime:
        # tiny IoT shards) are cheaper as a flat graph
        losses = []
        for s in range(n_steps):
            carry, loss = body(carry, (xb[:, s], yb[:, s]))
            losses.append(loss)
        params = carry[0]
        losses = jnp.stack(losses)
    else:
        xs = jnp.moveaxis(xb, 0, 1), jnp.moveaxis(yb, 0, 1)  # steps-major
        carry, losses = jax.lax.scan(body, carry, xs)
        params = carry[0]
    return params, losses.mean(axis=0)


@partial(jax.jit, static_argnames=("program", "n_steps", "lr"), donate_argnums=(0,))
def _cohort_epoch(params, xb, yb, program: ClientProgram, n_steps: int, lr: float):
    """Tree-major cohort epoch (see ``_cohort_epoch_body``).

    The params carry is donated: epochs chain ``params`` through repeated
    calls and never reuse the old value, so XLA may update the (C, D)-sized
    params (and with it the Adam carry) in place instead of
    double-buffering it.
    """
    return _cohort_epoch_body(params, xb, yb, program, n_steps, lr, "gemm")


@partial(
    jax.jit,
    static_argnames=("spec", "program", "n_steps", "lr", "impl"),
    donate_argnums=(0,),
)
def _cohort_epoch_flat(
    flat, xb, yb, spec, program: ClientProgram, n_steps: int, lr: float, impl: str = "gemm"
):
    """Flat-major cohort epoch: (C, D) in, (C, D) out, one dispatch.

    The device pipeline keeps model state as flat matrices end to end; the
    tree unravel/ravel happens INSIDE the jit so the per-leaf slices fuse
    with their consumers instead of materializing between dispatches, and
    the donated (C, D) carry can be updated in place across epochs.
    ``spec`` is the model's (hashable) ``TreeSpec``; ``program`` is equally
    hashable (frozen dataclass), so the jit cache is keyed on program
    identity and every registered workload shares this one entry point.
    """
    params = unravel_batched(spec, flat)
    params, loss = _cohort_epoch_body(params, xb, yb, program, n_steps, lr, impl)
    return ravel_batched(params), loss


register_jit("cohort_epoch", _cohort_epoch)
register_jit("cohort_epoch_flat", _cohort_epoch_flat)


@dataclasses.dataclass
class CohortResult:
    """Trained rows for one ``run_cohorts`` call, gather-friendly.

    Rows live in one (P_b, D_b) BLOCK per distinct program — flat rows of
    different architectures have different widths, so a mixed-program call
    cannot put every job in one matrix.  Homogeneous calls (the common
    case) produce exactly one block, exposed as :attr:`matrix`.
    """

    blocks: List["jnp.ndarray"]  # per-program (P_b, D_b) trained rows
    index: Dict[object, Tuple[int, int]]  # job tag -> (block, row)
    loss: Dict[object, float]

    @property
    def matrix(self) -> "jnp.ndarray":
        """The single block of a homogeneous call (legacy alias)."""
        if len(self.blocks) != 1:
            raise ValueError(
                f"CohortResult holds {len(self.blocks)} program blocks; "
                "use row()/gather() for mixed-program results"
            )
        return self.blocks[0]

    def row(self, tag) -> "jnp.ndarray":
        b, r = self.index[tag]
        return self.blocks[b][r]

    def gather(self, tags: Sequence) -> "jnp.ndarray":
        """(len(tags), D) sub-matrix in one device gather.  All tags must
        share one program block (callers aggregate per architecture)."""
        where = [self.index[t] for t in tags]
        bs = {b for b, _ in where}
        if len(bs) > 1:
            raise ValueError("gather() tags span program blocks")
        return self.blocks[bs.pop()][np.asarray([r for _, r in where])]


def _stack_starts(jobs: Sequence[LocalJob]) -> "jnp.ndarray":
    """Stack start rows deduplicating identical arrays.

    In a sync round most clients start from one of n_edges edge models, so
    stacking via unique-rows + gather costs O(n_edges) device ops instead of
    O(C) — the difference between the engine scaling and not at M >= 512.
    """
    uniq: Dict[int, int] = {}
    uniq_rows = []
    take = []
    for j in jobs:
        pos = uniq.get(id(j.start_flat))
        if pos is None:
            pos = len(uniq_rows)
            uniq[id(j.start_flat)] = pos
            uniq_rows.append(j.start_flat)
        take.append(pos)
    stacked = jnp.stack(uniq_rows)
    if len(uniq_rows) == len(jobs):
        return stacked
    return stacked[np.asarray(take)]


def run_cohorts(
    jobs: Sequence[LocalJob],
    program: ClientProgram,
    pack,
    store=None,
    telemetry=None,
) -> CohortResult:
    """Train every job, batching same-shape clients into vmapped cohorts.

    ``program``/``pack`` are the PRIMARY program and its
    ``engine.flatten.FlatPack`` — jobs whose clients carry a different
    program (heterogeneous-model populations) train with their own
    program's pack (``pack_for``) and land in their own result block.
    Multi-epoch schedules run epoch-by-epoch with the cohort's params
    carried across epochs, matching the reference's sequential-epoch
    semantics.

    ``store`` (optional): a ``DeviceShardStore``; per-epoch batches are
    gathered on device from the padded shard array (uploading only the
    int32 sample indices) instead of ``np.stack``-ing numpy shards on the
    host every epoch.  ``telemetry`` (optional
    ``repro.telemetry.Telemetry``) records one ``cohort_epoch`` span per
    cohort with the analytic FLOPs/bytes of the jitted epoch.
    """
    tel = telemetry if telemetry is not None else NULL_TELEMETRY
    program = program if program is not None else jobs[0].client.program

    def pack_of(prog):
        return pack if (prog == program and pack is not None) else pack_for(prog)

    groups: Dict[Tuple, List[LocalJob]] = {}
    passthrough: Dict[ClientProgram, List[LocalJob]] = {}
    block_of: Dict[ClientProgram, int] = {}
    for job in jobs:
        block_of.setdefault(job.client.program, len(block_of))
        if job.steps == 0:  # empty shard: params pass through untouched
            passthrough.setdefault(job.client.program, []).append(job)
            continue
        groups.setdefault(job.key, []).append(job)
    # per program block: trained cohort matrices in group-encounter order
    mats: Dict[ClientProgram, List[jnp.ndarray]] = {p: [] for p in block_of}
    offsets: Dict[ClientProgram, int] = {p: 0 for p in block_of}
    index: Dict[object, Tuple[int, int]] = {}
    loss_of: Dict[object, float] = {}
    for (prog, steps, epochs, batch, lr), members in groups.items():
        with tel.span(
            "cohort_epoch", program=prog.name, clients=len(members),
            epochs=epochs, steps=steps, batch=batch,
        ) as sp:
            if tel.enabled:
                tel.metrics.observe("cohort_size", len(members))
                need = float(steps * batch)
                occ = [min(len(j.client.shard), need) / need for j in members]
                tel.metrics.observe(
                    "cohort_padding_waste", 1.0 - sum(occ) / len(occ)
                )
            params = pack_of(prog).unravel_batched(_stack_starts(members))
            loss = jnp.zeros((len(members),), jnp.float32)
            cids = (
                np.asarray([j.client.cid for j in members], np.int64)
                if store is not None
                else None
            )
            for e in range(epochs):
                if store is not None:
                    xb, yb = store.gather(cids, np.stack([j.idx[e] for j in members]))
                else:
                    xb = jnp.asarray(np.stack([j.client.shard.x[j.idx[e]] for j in members]))
                    yb = jnp.asarray(np.stack([j.client.shard.y[j.idx[e]] for j in members]))
                if e == 0:
                    cost = tel.jit_cost(
                        "cohort_epoch", _cohort_epoch,
                        params, xb, yb, prog, steps, lr,
                    )
                    if cost:
                        sp.set(**cost)
                params, loss = _cohort_epoch(params, xb, yb, prog, steps, lr)
            mats[prog].append(pack_of(prog).ravel_batched(params))
        loss = np.asarray(loss)
        for c, job in enumerate(members):
            index[job.tag] = (block_of[prog], offsets[prog] + c)
            loss_of[job.tag] = float(loss[c])
        offsets[prog] += len(members)
    for prog, jobs_pt in passthrough.items():
        mats[prog].append(_stack_starts(jobs_pt))
        for c, job in enumerate(jobs_pt):
            index[job.tag] = (block_of[prog], offsets[prog] + c)
            loss_of[job.tag] = 0.0
        offsets[prog] += len(jobs_pt)
    if not block_of:
        dim = pack.dim if pack is not None else pack_for(program).dim
        return CohortResult([jnp.zeros((0, dim), jnp.float32)], {}, {})
    blocks = [None] * len(block_of)
    for prog, b in block_of.items():
        blocks[b] = (
            mats[prog][0] if len(mats[prog]) == 1 else jnp.concatenate(mats[prog], axis=0)
        )
    return CohortResult(blocks, index, loss_of)


@dataclasses.dataclass
class _PlanGroup:
    """One same-shape cohort of a ``CohortPlan`` after a round's draw."""

    members: np.ndarray  # (C,) participating client ids, in client order
    idx: np.ndarray  # (C, epochs, steps, batch) int32 sample indices
    steps: int
    batch: int
    lr: float
    program: ClientProgram = None  # the cohort's architecture

    @property
    def epochs(self) -> int:
        return self.idx.shape[1]


class CohortPlan:
    """Static cohort grouping for the sync engines' rounds.

    Which cohort a client falls into depends only on its shard size and its
    hyperparameters — the full (steps, local-epochs, batch-size, lr) tuple,
    so a HETEROGENEOUS population (per-client ``lr`` / ``batch_size`` /
    ``local_epochs``) splits into one fixed-shape cohort per distinct
    tuple while every cohort still trains in one vmapped dispatch.  The
    grouping (and each client's padded step count) is computed ONCE at
    engine construction; only the epoch count of clients that FOLLOW the
    schedule (``local_epochs=None``) is resolved at draw time.

    Per round, :meth:`draw` only consumes the numpy RNG stream —
    draw-for-draw like ``FLClient.local_update`` and in global client
    order, which is what keeps fixed-seed engine runs on the reference
    trajectory regardless of how clients are grouped — and fills per-group
    index tensors, with no per-round ``LocalJob``/``make_job`` objects.

    The plan keys cohorts on the clients' ``program`` as well: clients only
    stack into one (C, D) cohort when they train the SAME ``ClientProgram``
    (that is what makes the stacked rows meaningful), so a
    heterogeneous-model population splits into per-architecture cohorts
    exactly as heterogeneous hyperparameters split per tuple.  Each drawn
    ``_PlanGroup`` carries its cohort's program; ``plan.program`` stays the
    primary (constructor / first client's) program so two engines over
    different workloads can never share a grouping by accident.
    """

    def __init__(self, clients: Sequence[FLClient], program: ClientProgram | None = None):
        self.program = program if program is not None else clients[0].program
        self.sizes = np.array([len(c.shard) for c in clients], np.int64)
        self.steps = np.zeros(len(clients), np.int64)
        # per-client schedule override (None = follow the schedule's epochs)
        self._epochs_override: List[int | None] = [c.local_epochs for c in clients]
        self._single_step = [c.program.single_step for c in clients]
        self._group_key: Dict[int, Tuple] = {}
        for i, c in enumerate(clients):
            if self.sizes[i] == 0:
                continue
            self.steps[i] = c.plan_steps()
            self._group_key[i] = (c.program, int(self.steps[i]), c.batch_size, c.lr)

    def _epochs_of(self, i: int, schedule_epochs: int) -> int:
        if self._single_step[i]:
            return 1
        e = self._epochs_override[i]
        return e if e is not None else schedule_epochs

    def draw(
        self, rng: np.random.Generator, active: np.ndarray, epochs: int
    ) -> Tuple[List[_PlanGroup], np.ndarray]:
        """Returns (groups, passthrough) for the ``active`` clients.

        ``epochs`` is the schedule's ``local_steps`` — clients with their
        own ``local_epochs`` (or a ``single_step`` program) deviate from
        it and land in their own cohorts.  ``passthrough`` lists active
        clients with empty shards (they train zero steps and upload their
        start row).  RNG consumption replicates ``draw_batch_indices`` per
        active client, in client order, each client drawing ITS epoch
        count — exactly the reference simulator's stream.
        """
        members: Dict[Tuple, List[int]] = {}
        passthrough: List[int] = []
        for i in np.nonzero(active)[0]:
            if self.sizes[i] == 0:
                passthrough.append(int(i))
            else:
                key = self._group_key[int(i)] + (self._epochs_of(int(i), epochs),)
                members.setdefault(key, []).append(int(i))
        groups = [
            _PlanGroup(
                members=np.asarray(ids, np.int64),
                idx=np.zeros((len(ids), e, steps, batch), np.int32),
                steps=steps,
                batch=batch,
                lr=lr,
                program=prog,
            )
            for (prog, steps, batch, lr, e), ids in members.items()
        ]
        slot = {}
        for g in groups:
            for c, i in enumerate(g.members):
                slot[int(i)] = (g, c)
        # the draws themselves MUST run in global client order
        for i in np.nonzero(active)[0]:
            if self.sizes[i] == 0:
                continue
            g, c = slot[int(i)]
            n = int(self.sizes[i])
            need = g.steps * g.batch
            for e in range(g.epochs):
                idx = rng.permutation(n)
                if need > n:  # pad by resampling
                    idx = np.concatenate([idx, rng.integers(0, n, need - n)])
                g.idx[c, e] = idx[:need].reshape(g.steps, g.batch)
        return groups, np.asarray(passthrough, np.int64)


class StreamCohortPlan:
    """``CohortPlan`` over an analytic population: no per-client objects.

    ``CohortPlan`` loops over M ``FLClient`` objects at construction — an
    O(M) python pass that alone breaks the streaming budget at M=1M.  The
    stream plan takes the source's (M,) ``sizes`` array plus homogeneous
    hyperparameters and derives every client's padded step count in one
    vectorized ``searchsorted`` over the step buckets.  :meth:`draw` then
    works on the round's *member id list* (the cohort) instead of an (M,)
    active mask: RNG consumption replicates ``draw_batch_indices`` per
    member, in ascending client order — draw-for-draw what ``CohortPlan``
    consumes for the same member set, so stream and sync cohort runs share
    one trajectory.
    """

    def __init__(
        self,
        sizes: np.ndarray,
        program: ClientProgram,
        *,
        batch_size: int = 10,
        lr: float = 1e-3,
        max_steps: int = 128,
    ):
        from repro.federated.client import _BUCKETS

        self.program = program
        self.batch = int(batch_size)
        self.lr = float(lr)
        self.max_steps = int(max_steps)
        # shares the source's (M,) sizes array — the plan holds no O(M)
        # state of its own; step buckets are derived per cohort on demand
        self.sizes = np.asarray(sizes)
        self._buckets = np.asarray(_BUCKETS, np.int64)

    def steps_for(self, members: np.ndarray) -> np.ndarray:
        """Padded step count per member (FLClient._bucket, vectorized)."""
        s = self.sizes[members].astype(np.int64)
        if self.program.single_step:
            return (s > 0).astype(np.int64)
        raw = np.clip((s + self.batch - 1) // self.batch, 1, self.max_steps)
        pos = np.minimum(
            np.searchsorted(self._buckets, raw, side="left"),
            len(self._buckets) - 1,
        )
        return np.where(s > 0, self._buckets[pos], 0)

    def draw(
        self, rng: np.random.Generator, members: np.ndarray, epochs: int
    ) -> Tuple[List[_PlanGroup], np.ndarray]:
        """(groups, passthrough) for the cohort ``members`` (sorted ids)."""
        epochs = 1 if self.program.single_step else int(epochs)
        members = np.asarray(members, np.int64)
        steps_of = dict(zip(members.tolist(), self.steps_for(members).tolist()))
        grouped: Dict[int, List[int]] = {}
        passthrough: List[int] = []
        for i in members:
            if self.sizes[i] == 0:
                passthrough.append(int(i))
            else:
                grouped.setdefault(steps_of[int(i)], []).append(int(i))
        groups = [
            _PlanGroup(
                members=np.asarray(ids, np.int64),
                idx=np.zeros((len(ids), epochs, steps, self.batch), np.int32),
                steps=steps,
                batch=self.batch,
                lr=self.lr,
                program=self.program,
            )
            for steps, ids in grouped.items()
        ]
        slot = {}
        for g in groups:
            for c, i in enumerate(g.members):
                slot[int(i)] = (g, c)
        for i in np.asarray(members, np.int64):  # draws in global client order
            if self.sizes[i] == 0:
                continue
            g, c = slot[int(i)]
            n = int(self.sizes[i])
            need = g.steps * g.batch
            for e in range(epochs):
                idx = rng.permutation(n)
                if need > n:
                    idx = np.concatenate([idx, rng.integers(0, n, need - n)])
                g.idx[c, e] = idx[:need].reshape(g.steps, g.batch)
        return groups, np.asarray(passthrough, np.int64)

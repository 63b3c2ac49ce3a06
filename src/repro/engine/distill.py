"""Distillation aggregation: fusing heterogeneous-architecture edge models.

The paper's hierarchy assumes every EU trains the same model, so edge
FedAvg can average parameter vectors directly.  Real IoT fleets are
capability-skewed: strong EUs can carry the CNN, weak ones only an MLP,
text nodes a token LM.  Parameter averaging across architectures is
meaningless — but their LOGITS on shared data are comparable whenever the
programs emit the same alphabet (class scores, or vocab scores for the
sequence LMs).

This module implements the edge-side fuse (FedMD / FedDF-style ensemble
distillation on a small public shard):

  1. per-architecture FedAvg has already produced one edge model per
     program group (``hier_segment_aggregate`` within each group — that
     part of the paper's pipeline is unchanged);
  2. the TEACHER is the group ensemble: mean of every group model's
     temperature-softened distribution on a public batch, computed from
     the PRE-fuse models (all students see the same fixed targets);
  3. each group's STUDENT takes ``DistillSpec.steps`` plain-SGD steps on
     the soft cross-entropy against those targets — plain SGD, not the
     program's local optimizer, so the fuse is stateless, symmetric
     across groups, and exactly reproducible in the flat and tree forms.

Two equivalent implementations, pinned together by ``tests/test_distill``:

  * ``distill_edge``      — tree-form, one edge at a time: the readable
                            reference used by
                            ``federated.simulation.HeteroHFLSimulation``;
  * ``distill_fuse_flat`` — flat-form, vmapped over ALL edges at once on
                            (E, D_g) matrices: what the engines run.  One
                            jitted dispatch per (group, step-count) —
                            teacher forwards for every edge in one vmap.

With a single group the ensemble teacher is the student itself and the
fuse would be self-distillation; the engines skip the fuse entirely for
homogeneous populations, which is what keeps those runs bit-identical to
the pre-distillation pipeline.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry import NULL_TELEMETRY, register_jit
from repro.utils.tree import TreeSpec, tree_unravel


@dataclasses.dataclass(frozen=True)
class DistillSpec:
    """Knobs of one edge-side distillation fuse (frozen: rides jit keys).

    ``steps`` SGD steps of size ``lr`` on batches of ``batch`` public
    samples; ``temperature`` softens both teacher and student
    distributions (the classic T^2 gradient scale is applied so the KD
    gradient magnitude is temperature-invariant); ``weight`` scales the
    whole KD loss — the knob between "trust your group's FedAvg" (small)
    and "trust the ensemble" (large).
    """

    steps: int = 4
    batch: int = 16
    temperature: float = 2.0
    lr: float = 1e-3
    weight: float = 1.0

    def __post_init__(self):
        if self.steps < 1:
            raise ValueError(f"distill steps must be >= 1, got {self.steps}")
        if self.batch < 1:
            raise ValueError(f"distill batch must be >= 1, got {self.batch}")
        if self.temperature <= 0.0:
            raise ValueError(f"temperature must be > 0, got {self.temperature}")


def draw_public_batches(rng, sizes, spec: DistillSpec):
    """Per-edge public-shard sample indices for one distillation fuse.

    One ``(steps, batch)`` integer draw per edge, in edge order — the
    reference simulator and the sync and async engines replicate this
    stream draw-for-draw, which is what keeps their fuses on identical
    batches.  Returns an ``(E, steps, batch)`` int32 index tensor.
    """
    return np.stack(
        [rng.integers(0, int(n), (spec.steps, spec.batch)) for n in sizes]
    ).astype(np.int32)


def soft_targets(programs: Sequence, params_list: Sequence, x, temperature: float):
    """Ensemble teacher distribution on one public batch.

    Mean over groups of ``softmax(apply_logits / T)`` — softened over the
    LAST axis, so classifier ``(B, K)`` and sequence ``(B, S, V)`` logits
    work identically.  Callers treat the result as a constant target
    (it is computed from pre-fuse models, outside the student grad).
    """
    probs = None
    for prog, params in zip(programs, params_list):
        p = jax.nn.softmax(prog.apply_logits(params, x) / temperature, axis=-1)
        probs = p if probs is None else probs + p
    return probs / len(programs)


def kd_loss(program, params, x, targets, spec: DistillSpec):
    """Soft cross-entropy of the student against the ensemble targets.

    ``-T^2 * weight * mean(sum(targets * log_softmax(student / T)))`` —
    the same gradient as the KL form (the teacher-entropy term is constant
    in the student), averaged over every leading axis.
    """
    logp = jax.nn.log_softmax(
        program.apply_logits(params, x) / spec.temperature, axis=-1
    )
    ce = -jnp.mean(jnp.sum(targets * logp, axis=-1))
    return spec.weight * spec.temperature**2 * ce


# ---------------------------------------------------------------------------
# tree form: the reference simulator's per-edge fuse
# ---------------------------------------------------------------------------
def distill_edge(
    programs: Sequence, params_list: Sequence, xb, spec: DistillSpec
) -> Tuple[List, List[float]]:
    """Fuse one edge's per-group models on its public batches.

    ``xb`` is the edge's drawn public data, ``(steps, B, *feat)``.  Returns
    the post-fuse parameter trees (same order as ``programs``) and each
    group's mean KD loss over the steps.  Teachers are the PRE-fuse models
    on every step's batch; students then descend independently.
    """
    xb = jnp.asarray(xb)
    targets = [
        soft_targets(programs, params_list, xb[s], spec.temperature)
        for s in range(spec.steps)
    ]
    fused, losses = [], []
    for prog, params in zip(programs, params_list):
        p = params
        total = 0.0
        for s in range(spec.steps):
            loss, grads = jax.value_and_grad(
                lambda q: kd_loss(prog, q, xb[s], targets[s], spec)
            )(p)
            p = jax.tree.map(lambda a, g: a - spec.lr * g, p, grads)
            total += float(loss)
        fused.append(p)
        losses.append(total / spec.steps)
    return fused, losses


# ---------------------------------------------------------------------------
# flat form: all edges fused in one vmapped program per group
# ---------------------------------------------------------------------------
def _ensemble_targets_flat(mats, xb_s, programs, specs, temperature):
    """Teacher targets for step s on every edge at once: (E, B..., K)."""
    probs = None
    for prog, spec, mat in zip(programs, specs, mats):

        def logits_one(row, x, prog=prog, spec=spec):
            return prog.apply_logits(tree_unravel(spec, row), x)

        p = jax.nn.softmax(jax.vmap(logits_one)(mat, xb_s) / temperature, axis=-1)
        probs = p if probs is None else probs + p
    return probs / len(programs)


@partial(jax.jit, static_argnames=("programs", "specs", "dspec"))
def _kd_targets_all(mats, xb, programs: Tuple, specs: Tuple, dspec: DistillSpec):
    """Ensemble teacher targets for every step at once: (steps, E, B..., K).

    Computed ONCE per fuse from the pre-fuse teacher matrices — every
    student group distills against this same tensor, so the G teacher
    forwards per step are not repeated per student."""
    return jnp.stack(
        [
            _ensemble_targets_flat(mats, xb[s], programs, specs, dspec.temperature)
            for s in range(dspec.steps)
        ]
    )


@partial(jax.jit, static_argnames=("prog", "spec", "dspec"))
def _distill_fuse_one(flat, xb, targets, prog, spec: TreeSpec, dspec: DistillSpec):
    """One group's students on every edge: (E, D_g) in, (E, D_g) out.

    ``xb``/``targets`` are the (steps, E, B, *feat) public batches and the
    fixed (steps, E, B..., K) teacher tensor.  The step count is tiny and
    static, so the loop unrolls into one graph; per-edge gradients come
    from one vmap — the "vmapped teacher forward over group
    representatives" the distillation layer is built around.
    """
    losses = []
    for s in range(dspec.steps):

        def kd_one(row, x, t):
            return kd_loss(prog, tree_unravel(spec, row), x, t, dspec)

        loss, grads = jax.vmap(jax.value_and_grad(kd_one))(flat, xb[s], targets[s])
        flat = flat - dspec.lr * grads
        losses.append(loss)
    return flat, jnp.stack(losses).mean()


register_jit("kd_targets", _kd_targets_all)
register_jit("kd_fuse_one", _distill_fuse_one)


def distill_fuse_flat(
    programs: Sequence,
    specs: Sequence[TreeSpec],
    mats: Sequence,
    xb,
    spec: DistillSpec,
    telemetry=None,
) -> Tuple[List, List[float]]:
    """Fuse every edge's per-group models in one pass per group.

    ``mats[g]`` is group g's (E, D_g) edge matrix, ``xb`` the
    (E, steps, B, *feat) public batches (edge-major, as the public shard
    store gathers them).  Returns the post-fuse matrices and per-group mean
    KD losses.  Every student distills from the same pre-fuse teachers
    (one shared target tensor), so group update order cannot matter.
    ``telemetry`` records the ``kd_fuse`` span (all three engine call sites
    route through here) with the fused analytic cost of the teacher and
    per-group student programs.
    """
    tel = telemetry if telemetry is not None else NULL_TELEMETRY
    with tel.span("kd_fuse", groups=len(programs), steps=spec.steps) as span:
        xb = jnp.moveaxis(jnp.asarray(xb), 0, 1)  # (steps, E, B, *feat)
        programs, specs, mats = tuple(programs), tuple(specs), tuple(mats)
        cost = tel.jit_cost(
            "kd_targets", _kd_targets_all, mats, xb, programs, specs, spec
        )
        targets = _kd_targets_all(mats, xb, programs, specs, spec)
        out, losses = [], []
        for gi in range(len(programs)):
            c = tel.jit_cost(
                "kd_fuse_one", _distill_fuse_one,
                mats[gi], xb, targets, programs[gi], specs[gi], spec,
            )
            if c:
                cost = {k: cost.get(k, 0.0) + v for k, v in c.items()} if cost else c
            fused, loss = _distill_fuse_one(
                mats[gi], xb, targets, programs[gi], specs[gi], spec
            )
            out.append(fused)
            losses.append(float(loss))
        if cost:
            span.set(**cost)
        if tel.enabled:
            for gi, loss in enumerate(losses):
                tel.metrics.observe("kd_loss", loss)
    return out, losses


def check_public_shards(public_shards, n_edges: int) -> None:
    """One NON-EMPTY public shard per edge — shared by the engines and the
    reference simulator so a future relaxation cannot diverge them."""
    if public_shards is None or len(public_shards) != n_edges:
        raise ValueError(
            f"distillation needs one public shard per edge ({n_edges}), got "
            f"{None if public_shards is None else len(public_shards)}"
        )
    if any(len(s) == 0 for s in public_shards):
        raise ValueError("distillation public shards must be non-empty")


def check_distillable(programs: Sequence) -> None:
    """Distillation needs one shared logit alphabet and one shard layout."""
    k = {p.n_classes for p in programs}
    if len(k) > 1:
        raise ValueError(
            f"distillation fuse needs one shared label alphabet, got n_classes={sorted(k)}"
        )
    feats = {(p.feat_shape, jnp.dtype(p.feat_dtype).name) for p in programs}
    if len(feats) > 1:
        raise ValueError(
            "distillation fuse needs one shared public-shard layout, got "
            f"{sorted(feats)}"
        )
    # sequence programs score a VOCAB, not the topic alphabet n_classes
    # reports — their logit axis must agree too
    vocab = {getattr(getattr(p, "cfg", None), "vocab_size", None) for p in programs}
    if len(vocab) > 1:
        raise ValueError(
            f"distillation fuse needs one shared logit alphabet, got vocab sizes {sorted(map(str, vocab))}"
        )

"""Flat-buffer views of client model pytrees.

The batched engine moves aggregation off the per-leaf ``jax.tree.map`` path
and onto a single ``(N, D)`` update matrix so the FedAvg reduction can run
through the Pallas kernels in one HBM pass.  ``FlatPack`` caches the layout
spec of the model once and converts trees <-> rows; two weighted-average
primitives sit on top, each with two backends ("pallas" routes through the
kernels, "reference" through plain-XLA contractions):

  * ``flat_mean``         — one weighted average over an (N, D) matrix
                            (``kernels.hier_aggregate``); tiny-N calls are
                            routed to a jitted reference contraction so
                            shape-churning callers (DCA start averaging,
                            async quorum flushes with 1-3 rows) do not
                            compile a fresh kernel per shape;
  * ``flat_segment_mean`` — ALL segments of an (N, D) matrix at once ->
                            (E, D) (``kernels.segment_aggregate``); large
                            segment counts route to the O(N*D)
                            ``segment_sum`` formulation instead of the
                            O(E*N*D) one-hot contraction.

Consistency tests (``tests/test_engine.py``, ``tests/test_kernels.py``)
pin the backends together.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.hier_aggregate import hier_aggregate
from repro.kernels.ops import hier_aggregate as hier_aggregate_jit
from repro.kernels.ops import hier_segment_aggregate as hier_segment_aggregate_jit
from repro.kernels.ref import hier_segment_aggregate_ref
from repro.kernels.segment_aggregate import hier_segment_aggregate
from repro.telemetry import register_jit
from repro.utils.tree import TreeSpec, tree_ravel, tree_spec, tree_unravel

BACKENDS = ("pallas", "reference")

# flat_mean calls with at most this many rows skip the pallas kernel: the
# kernel's jit cache is keyed on (N, D), so host loops that average a
# handful of varying-count rows (DCA starts over 1-3 edges, async quorum
# flushes) would compile a fresh kernel per N.  A plain contraction at
# these sizes is bandwidth-trivial and compiles in milliseconds.
_SMALL_N = 8

# one-hot segment contraction costs O(E*N*D); past this many segments the
# segment_sum scatter-add (O(N*D)) wins even on accelerators.
_MAX_ONEHOT_SEGMENTS = 32


class FlatPack:
    """Tree <-> flat-row converter bound to one model layout.

    Works for ANY client program's parameter pytree (CNN dicts, the MLP's
    dense pairs, the transformer's tuple-of-stacked-blocks), with one
    requirement checked up front: every leaf must share one dtype.  The
    flat row is a single concatenated buffer, so mixed-dtype trees would
    silently promote on ravel and cast back on unravel — exact for the
    uniform-fp32 programs this repo trains, lossy in general.
    """

    def __init__(self, template_tree):
        self.spec: TreeSpec = tree_spec(template_tree)
        if len(set(self.spec.dtypes)) > 1:
            raise ValueError(
                "FlatPack requires a uniform leaf dtype for an exact "
                f"ravel/unravel round-trip; got {sorted(set(map(str, self.spec.dtypes)))}"
            )

    @property
    def dim(self) -> int:
        return self.spec.total_size

    def ravel(self, tree) -> jnp.ndarray:
        flat, spec = tree_ravel(tree)
        if spec.shapes != self.spec.shapes:
            raise ValueError("tree layout does not match FlatPack template")
        return flat

    def unravel(self, flat: jnp.ndarray):
        # jitted (cache keyed on the spec): one dispatch instead of a
        # slice+reshape+astype chain per leaf — this sits on the engines'
        # per-round eval path
        return _tree_unravel_jit(flat, spec=self.spec)

    def stack(self, trees: Sequence) -> jnp.ndarray:
        """Ravel N trees into the (N, D) update matrix."""
        return jnp.stack([self.ravel(t) for t in trees], axis=0)

    def ravel_batched(self, stacked_tree) -> jnp.ndarray:
        """Tree with a leading cohort axis C on every leaf -> (C, D) matrix."""
        return ravel_batched(stacked_tree)

    def unravel_batched(self, mat: jnp.ndarray):
        """(C, D) matrix -> tree with a leading cohort axis C on every leaf."""
        return unravel_batched(self.spec, mat)


def ravel_batched(stacked_tree) -> jnp.ndarray:
    """Tree with a leading cohort axis C on every leaf -> (C, D) matrix.

    One reshape+concat per LEAF (not per client) — the cheap direction
    for engine hot loops.
    """
    leaves = jax.tree.leaves(stacked_tree)
    return jnp.concatenate([l.reshape(l.shape[0], -1) for l in leaves], axis=1)


def unravel_batched(spec: TreeSpec, mat: jnp.ndarray):
    """(C, D) matrix -> tree with a leading cohort axis C on every leaf.

    ``spec`` is hashable, so this is usable inside jitted functions with the
    spec as a static argument (``engine.cohort._cohort_epoch_flat``)."""
    c = mat.shape[0]
    leaves = []
    off = 0
    for shape, dtype, size in zip(spec.shapes, spec.dtypes, spec.sizes):
        leaves.append(
            jax.lax.slice_in_dim(mat, off, off + size, axis=1)
            .reshape((c,) + shape)
            .astype(dtype)
        )
        off += size
    return jax.tree.unflatten(spec.treedef, leaves)


def compress_flat_upload(spec, errors: dict, key, start_row, trained_row):
    """Apply a ``CompressionSpec`` to a flat model delta with error feedback.

    Shared by both engines.  The spec is applied to the whole (D,) delta in
    one shot — a single global top-k over all parameters — unlike the
    reference simulator's per-leaf application.  ``errors[key]`` holds the
    client's error-feedback state and is updated in place.
    """
    if spec is None or spec.kind == "none":
        return trained_row
    delta = trained_row - start_row
    sparse, err = spec.apply(delta, errors.get(key))
    errors[key] = err
    return start_row + sparse


@jax.jit
def _small_mean(updates: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """Jitted reference contraction for tiny-N pallas-backend calls
    (same normalization guard as ``hier_aggregate``)."""
    w = weights.astype(jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-30)
    # HIGHEST keeps FedAvg float32 on the TPU, whose default rounds to bf16
    return jnp.tensordot(
        w, updates.astype(jnp.float32), axes=1, precision=jax.lax.Precision.HIGHEST
    ).astype(updates.dtype)


def flat_mean(
    updates: jnp.ndarray,
    weights,
    *,
    backend: str = "pallas",
    block: int = 4096,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Weighted average over the leading axis of an (N, D) update matrix."""
    if backend == "pallas":
        if interpret is not None:  # explicit mode: bypass the jit cache
            return hier_aggregate(updates, jnp.asarray(weights), block=block, interpret=interpret)
        if updates.shape[0] <= _SMALL_N:
            return _small_mean(updates, jnp.asarray(weights))
        # the jitted wrapper caches the (interpret-emulated off-TPU) kernel
        # per (N, D) shape — the hot path for repeated engine rounds
        return hier_aggregate_jit(updates, jnp.asarray(weights), block=block)
    if backend == "reference":
        w = jnp.asarray(weights, dtype=jnp.float32)
        w = w / jnp.sum(w)
        out = jnp.tensordot(
            w, updates.astype(jnp.float32), axes=1, precision=jax.lax.Precision.HIGHEST
        )
        return out.astype(updates.dtype)
    raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")


_segment_mean_ref_jit = partial(jax.jit, static_argnames=("n_segments",))(
    hier_segment_aggregate_ref
)


@partial(jax.jit, static_argnames=("spec",))
def _tree_unravel_jit(flat, spec: TreeSpec):
    return tree_unravel(spec, flat)


def flat_segment_mean(
    updates: jnp.ndarray,
    seg_ids,
    weights,
    n_segments: int,
    *,
    backend: str = "pallas",
    block: int = 4096,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Every segment's weighted average at once: (N, D) -> (n_segments, D).

    The device-resident engines use this for per-edge FedAvg (segments =
    edges) and DCA start averaging (segments = clients) with STATIC shapes:
    membership is fixed by the assignment matrix, and per-round variation
    (participation, empty edges) travels in the weights, so repeated rounds
    hit one compiled program.  Empty / zero-weight segments return zero
    rows; callers overlay prior state.
    """
    if backend == "pallas" and interpret is not None:
        # explicit mode always honors the kernel (no jit cache, no segment
        # count routing) — this is the path parity tests rely on
        return hier_segment_aggregate(
            updates, jnp.asarray(seg_ids), jnp.asarray(weights), n_segments,
            block=block, interpret=interpret,
        )
    if backend == "pallas" and n_segments <= _MAX_ONEHOT_SEGMENTS:
        if jax.default_backend() == "tpu":
            return hier_segment_aggregate_jit(
                updates, jnp.asarray(seg_ids), jnp.asarray(weights), n_segments,
                block=block,
            )
        # off-TPU the kernel would run in interpret emulation, which is a
        # correctness tool, not a fast path — fall through to segment_sum
    if backend in BACKENDS:
        # large-E and off-TPU pallas calls deliberately share this
        # scatter-add path
        return _segment_mean_ref_jit(
            updates, jnp.asarray(seg_ids), jnp.asarray(weights), n_segments=n_segments
        )
    raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")


# jit compile accounting (telemetry): module-level jitted entry points of the
# flat-buffer aggregation layer.  The compile-count regression guard in
# tests/test_telemetry.py pins their cache growth per engine round — in
# particular that tiny-N ``flat_mean`` calls route to ``_small_mean`` and
# never touch the pallas wrapper's cache off-TPU.
register_jit("small_mean", _small_mean)
register_jit("segment_mean_ref", _segment_mean_ref_jit)
register_jit("tree_unravel", _tree_unravel_jit)
register_jit("hier_aggregate", hier_aggregate_jit)
register_jit("hier_segment_aggregate", hier_segment_aggregate_jit)

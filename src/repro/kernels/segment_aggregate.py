"""Segmented hierarchical-aggregation Pallas kernel.

``hier_aggregate`` reduces one edge's clients to one row; a cloud round
needs that reduction for EVERY edge, and dispatching E differently-shaped
``(N_j, D)`` kernels re-compiles per edge size and walks HBM E times.  This
kernel computes all edges at once: given the full ``(N, D)`` update matrix,
per-row segment ids, and per-row weights, it produces the ``(E, D)`` matrix
of weighted FedAvg results (paper eq. 6/8 applied per edge) in ONE pass
over the updates.

The segment reduction is phrased as a one-hot contraction: a normalized
``(E, N)`` weight matrix ``W`` with ``W[e, i] = w_i / sum_{seg(k)=e} w_k``
if ``seg(i) == e`` else 0 is built once (it is O(E*N) scalars), and each
grid step multiplies one ``(E, row tile)`` block of it against the matching
``(row tile, block)`` VMEM slab of updates on the MXU, accumulating into an
f32 VMEM accumulator across the row tiles (``hier_aggregate.tiling``).  The
update matrix is read from HBM exactly once regardless of E, the
fast-memory footprint does not grow with N, and the output shape is
static, so repeated rounds never re-compile.

Rows whose segment is empty (or whose weights sum to ~0) come back as
zeros; callers overlay prior state (the engines keep the previous edge
model for edges with no participants).

For large segment counts the O(E*N*D) one-hot contraction wastes compute
against the O(N*D) scatter-add; ``hier_segment_aggregate_ref`` (a
``jax.ops.segment_sum`` formulation) is the reference oracle AND the
preferred path in that regime — ``engine.flatten.flat_segment_mean`` does
the routing.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hier_aggregate import COMPILER_PARAMS, pad_updates, round_up, tiling


def _seg_kernel(w_ref, x_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]  # (E, row_tile) normalized one-hot weights, f32
    x = x_ref[...].astype(jnp.float32)  # (row_tile, block)
    # HIGHEST: at default precision the TPU's MXU rounds f32 operands to
    # bf16, which puts ~1e-3 relative error on every edge model
    acc_ref[...] += jnp.dot(w, x, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _segment_weight_matrix(seg_ids: jnp.ndarray, weights: jnp.ndarray, n_segments: int):
    """(E, N) matrix of per-segment-normalized weights (zero rows for empty
    segments); O(E*N) scalars, built outside the grid loop."""
    w = weights.astype(jnp.float32)
    onehot = (seg_ids[None, :] == jnp.arange(n_segments, dtype=seg_ids.dtype)[:, None])
    ow = jnp.where(onehot, w[None, :], 0.0)
    return ow / jnp.maximum(ow.sum(axis=1, keepdims=True), 1e-30)


def hier_segment_aggregate(
    updates: jnp.ndarray,
    seg_ids: jnp.ndarray,
    weights: jnp.ndarray,
    n_segments: int,
    *,
    block: int = 4096,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """updates: (N, D); seg_ids, weights: (N,). Returns (n_segments, D) of
    per-segment weighted averages; empty segments return zeros.

    Knobs: ``block`` — VMEM tile width over D (clamped to D; D is padded
    to a multiple so any D works); ``interpret`` — ``True`` runs the
    Pallas interpreter (correctness oracle, any backend), ``False``
    forces hardware lowering (TPU), ``None`` (default) auto-selects:
    hardware on TPU, interpreter elsewhere.  Callers that want speed
    off-TPU should route through ``engine.flatten.flat_segment_mean``,
    which picks the ``segment_sum`` formulation instead.
    """
    n, d = updates.shape
    if n == 0 or d == 0:
        return jnp.zeros((n_segments, d), updates.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bn, np_, bd, dp = tiling(n, d, block)
    ep = round_up(n_segments, 8)  # pad segments to the sublane tiling
    wmat = _segment_weight_matrix(jnp.asarray(seg_ids), jnp.asarray(weights), n_segments)
    wmat = jnp.pad(wmat, ((0, ep - n_segments), (0, np_ - n)))
    out = pl.pallas_call(
        _seg_kernel,
        grid=(dp // bd, np_ // bn),
        in_specs=[
            pl.BlockSpec((ep, bn), lambda i, k: (0, k)),
            pl.BlockSpec((bn, bd), lambda i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((ep, bd), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((ep, dp), updates.dtype),
        scratch_shapes=[pltpu.VMEM((ep, bd), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(wmat, pad_updates(updates, np_, dp))
    return out[:n_segments, :d]

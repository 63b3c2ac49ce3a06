"""Hierarchical weighted-aggregation Pallas kernel.

The FedAvg/edge aggregation hot spot (paper eq. 6/8): out = sum_n w_n x_n
over N client updates of D parameters.  The (N, D) update matrix is walked
in (row tile, column tile) VMEM blocks: the grid's inner axis runs over the
row tiles and accumulates each block's weighted column sums into an f32
VMEM accumulator, which is written out after the last row tile — one HBM
pass over the updates, and a fast-memory footprint fixed by the tile sizes
whatever the cohort size N.

Weights are pre-normalized outside the kernel (they are O(N) scalars); pad
rows carry weight 0.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Update-matrix rows per VMEM tile.  With the default 4096-wide column tile
# an f32 input block is 4 MiB (8 MiB double-buffered), inside v5e's default
# scoped VMEM limit at any N.
ROW_TILE = 256


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tiling(n: int, d: int, block: int) -> Tuple[int, int, int, int]:
    """``(row_tile, padded_n, col_tile, padded_d)`` for an (N, D) update
    matrix.  A cohort that fits one row tile is one full-height block and
    needs no row padding; a larger one pads to whole ``ROW_TILE`` tiles
    (lane-aligned, so the weight blocks tile too).  Columns pad to whole
    column tiles."""
    bn = min(n, ROW_TILE)
    bd = min(block, d)
    return bn, round_up(n, bn), bd, round_up(d, bd)


def pad_updates(updates: jnp.ndarray, n_pad: int, d_pad: int) -> jnp.ndarray:
    n, d = updates.shape
    if (n_pad, d_pad) == (n, d):
        return updates
    return jnp.pad(updates, ((0, n_pad - n), (0, d_pad - d)))


# grid = (column tiles, row tiles): columns are independent, rows reduce
COMPILER_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _agg_kernel(w_ref, x_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (row_tile, block)
    w = w_ref[...]  # (row_tile, 1) f32
    acc_ref[...] += jnp.sum(x * w, axis=0, keepdims=True)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def hier_aggregate(
    updates: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    block: int = 4096,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """updates: (N, D); weights: (N,). Returns the (D,) weighted average."""
    n, d = updates.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bn, np_, bd, dp = tiling(n, d, block)
    w = weights.astype(jnp.float32)
    w = w / jnp.maximum(w.sum(), 1e-30)
    w = jnp.pad(w, (0, np_ - n)).reshape(np_, 1)
    out = pl.pallas_call(
        _agg_kernel,
        grid=(dp // bd, np_ // bn),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i, k: (k, 0)),
            pl.BlockSpec((bn, bd), lambda i, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((1, bd), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), updates.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(w, pad_updates(updates, np_, dp))
    return out[0, :d]

"""Pallas kernel tests: shape/dtype sweeps, assert_allclose vs ref.py oracle
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.hier_aggregate import ROW_TILE, hier_aggregate
from repro.kernels.segment_aggregate import hier_segment_aggregate
from repro.kernels.topk_gating import topk_gating
from repro.kernels.ref import (
    flash_attention_ref,
    hier_aggregate_ref,
    hier_segment_aggregate_ref,
    topk_gating_ref,
)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,d,bq,bk",
    [
        (1, 128, 4, 4, 64, 64, 64),     # MHA
        (2, 256, 8, 2, 64, 128, 64),    # GQA 4:1
        (1, 256, 6, 6, 32, 64, 128),    # non-pow2 heads
        (2, 128, 4, 1, 128, 32, 32),    # MQA
    ],
)
def test_flash_attention_sweep(dtype, b, s, hq, hkv, d, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d)).astype(dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (2, 128, 4, 32)) for kk in ks)
    out = flash_attention(q, k, v, causal=True, window=window, block_q=32, block_k=32, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,block", [(4, 1000, 256), (13, 14789, 4096), (32, 512, 512), (600, 300, 128)])
def test_hier_aggregate_sweep(dtype, n, d, block):
    u = jax.random.normal(jax.random.PRNGKey(0), (n, d)).astype(dtype)
    w = jax.random.uniform(jax.random.PRNGKey(1), (n,), minval=0.05)
    out = hier_aggregate(u, w, block=block, interpret=True)
    ref = hier_aggregate_ref(u, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_hier_aggregate_is_fedavg():
    """Kernel implements exactly eq. 6: sigma-weighted average."""
    u = jnp.stack([jnp.full((100,), 1.0), jnp.full((100,), 3.0)])
    out = hier_aggregate(u, jnp.asarray([1.0, 3.0]), interpret=True)
    np.testing.assert_allclose(np.asarray(out), 2.5, rtol=1e-6)


# -- segmented aggregation (ISSUE 2) ---------------------------------------
RAGGED_CASES = [
    # seg_ids, n_segments: empty segment (2), single-client segment (4)
    (np.array([0, 0, 0, 1, 3, 3, 3, 3, 4]), 5),
    # all clients on one edge
    (np.zeros(9, int), 1),
    # every client its own edge + one empty trailing edge
    (np.arange(9), 10),
    # three VMEM row tiles, the last one part zero-weight padding; edge 6 empty
    (np.random.default_rng(0).integers(0, 6, 2 * ROW_TILE + 37), 7),
]


@pytest.mark.parametrize("seg,e", RAGGED_CASES)
@pytest.mark.parametrize("d,block", [(257, 64), (1000, 4096)])
def test_segment_aggregate_matches_reference_ragged(seg, e, d, block):
    n = len(seg)
    u = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    w = jax.random.uniform(jax.random.PRNGKey(1), (n,), minval=0.05)
    out = hier_segment_aggregate(u, jnp.asarray(seg), w, e, block=block, interpret=True)
    ref = hier_segment_aggregate_ref(u, jnp.asarray(seg), w, e)
    assert out.shape == (e, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_segment_aggregate_edge_semantics():
    """Empty segments are zero rows; single-client segments return the row
    exactly; a full single segment equals ``hier_aggregate``."""
    u = jax.random.normal(jax.random.PRNGKey(2), (9, 300))
    w = jax.random.uniform(jax.random.PRNGKey(3), (9,), minval=0.1)
    seg = jnp.asarray(np.array([0, 0, 0, 1, 3, 3, 3, 3, 4]))
    out = hier_segment_aggregate(u, seg, w, 5, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[2]), 0.0)  # empty edge
    np.testing.assert_array_equal(np.asarray(out[4]), np.asarray(u[8]))  # singleton
    one = hier_segment_aggregate(u, jnp.zeros(9, jnp.int32), w, 1, interpret=True)
    flat = hier_aggregate(u, w, interpret=True)
    np.testing.assert_allclose(np.asarray(one[0]), np.asarray(flat), atol=1e-6)


def test_segment_aggregate_is_per_edge_fedavg():
    """Each segment row is that edge's sigma-weighted average (paper eq. 6)."""
    u = jnp.stack([jnp.full((64,), v) for v in (1.0, 3.0, 10.0)])
    seg = jnp.asarray([0, 0, 1])
    out = hier_segment_aggregate(u, seg, jnp.asarray([1.0, 3.0, 7.0]), 2, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), 2.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out[1]), 10.0, rtol=1e-6)


@pytest.mark.parametrize("t,e,k,bt", [(64, 8, 2, 32), (200, 16, 4, 64), (100, 40, 8, 128)])
def test_topk_gating_sweep(t, e, k, bt):
    logits = jax.random.normal(jax.random.PRNGKey(0), (t, e)) * 2
    out = topk_gating(logits, k, block_t=bt, interpret=True)
    ref, _ = topk_gating_ref(logits, k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_topk_gating_properties():
    logits = jax.random.normal(jax.random.PRNGKey(2), (128, 16))
    out = np.asarray(topk_gating(logits, 4, interpret=True))
    # exactly k nonzeros per row, weights sum to 1
    assert (np.count_nonzero(out, axis=1) == 4).all()
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)

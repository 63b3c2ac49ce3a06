"""Hypothesis property tests for ``engine.flatten`` (ISSUE 5 satellite).

The FlatPack contract underpins every engine guarantee: ravel/unravel must
be EXACT (bit-level) for uniform-dtype trees, mixed-dtype trees must be
refused up front (a silent promote-and-cast round-trip would be lossy),
and ``flat_segment_mean`` must equal the plain segment_sum formulation on
arbitrary ragged segment maps.  Deterministic spot checks live in
``tests/test_engine.py``; these sweep randomized structures.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.engine.flatten import FlatPack, flat_segment_mean  # noqa: E402

_shapes = st.lists(
    st.lists(st.integers(1, 4), min_size=0, max_size=3), min_size=1, max_size=5
)


def _tree_of(shapes, seed, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {
        f"p{i}": jax.random.normal(k, tuple(s)).astype(dtype)
        for i, (k, s) in enumerate(zip(keys, shapes))
    }


@settings(max_examples=25, deadline=None)
@given(_shapes, st.integers(0, 2**31 - 1))
def test_flatpack_round_trip_exact(shapes, seed):
    """ravel -> unravel is the identity, bit for bit, for any structure."""
    tree = _tree_of(shapes, seed)
    pack = FlatPack(tree)
    flat = pack.ravel(tree)
    assert flat.shape == (sum(int(np.prod(s)) for s in shapes),)
    back = pack.unravel(flat)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=15, deadline=None)
@given(_shapes, st.integers(1, 4), st.integers(0, 2**31 - 1))
def test_flatpack_batched_round_trip_exact(shapes, cohort, seed):
    """The (C, D) batched forms agree with per-row ravel/unravel."""
    trees = [_tree_of(shapes, seed + c) for c in range(cohort)]
    pack = FlatPack(trees[0])
    mat = pack.stack(trees)
    assert mat.shape == (cohort, pack.dim)
    stacked = pack.unravel_batched(mat)
    np.testing.assert_array_equal(np.asarray(pack.ravel_batched(stacked)), np.asarray(mat))
    for c, tree in enumerate(trees):
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(
            jax.tree.map(lambda l: l[c], stacked)
        )):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=20, deadline=None)
@given(
    _shapes,
    # dtypes that stay distinct from the float32 leaves with x64 off
    # ("float64" would be made float32, leaving the tree uniform)
    st.sampled_from(["float16", "bfloat16", "int32", "int8"]),
    st.integers(0, 100),
)
def test_flatpack_rejects_mixed_dtype_trees(shapes, other_dtype, seed):
    """Any second leaf dtype is refused up front — the flat buffer would
    silently promote on ravel and cast back on unravel."""
    tree = _tree_of(shapes, seed)
    tree["odd"] = jnp.zeros((2,), jnp.dtype(other_dtype))
    with pytest.raises(ValueError):
        FlatPack(tree)


@settings(max_examples=25, deadline=None)
@given(
    st.integers(1, 12),  # rows
    st.integers(1, 24),  # features
    st.integers(1, 6),  # segments
    st.integers(0, 2**31 - 1),
)
def test_flat_segment_mean_matches_segment_sum_reference(n, d, e, seed):
    """Both backends equal the per-segment weighted mean computed leaf-wise
    in numpy, over random ragged segment maps — including segments that
    receive no rows at all (those must come back as zero rows)."""
    rng = np.random.default_rng(seed)
    u = rng.normal(size=(n, d)).astype(np.float32)
    seg = rng.integers(0, e, n)
    w = rng.uniform(0.1, 2.0, n).astype(np.float32)
    want = np.zeros((e, d), np.float32)
    for j in range(e):
        m = seg == j
        if m.any():
            want[j] = (u[m] * w[m, None]).sum(0) / w[m].sum()
    for backend in ("pallas", "reference"):
        out = np.asarray(
            flat_segment_mean(jnp.asarray(u), seg, w, e, backend=backend)
        )
        np.testing.assert_allclose(out, want, atol=1e-5)

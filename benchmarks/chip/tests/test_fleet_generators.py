"""The benchmark's own fleet generators (``gen_fleet``) against the
program's lazy population and cohort draw, bit for bit: the plain
reference trains on the former, the stream engine on the latter."""
import numpy as np
import pytest

import gen_fleet

SEED = 2**31 + 11
M = 4096


@pytest.fixture(scope="module")
def pair():
    from repro.federated import build_scenario

    sc = build_scenario("seizure", lazy=True, n_eus=M, n_edges=8, seed=SEED,
                        n_test_per_class=1)
    s = sc.source
    return s, gen_fleet.HealthFleet(SEED, M, s.n_classes, s.length, s.channels,
                                    s.min_per_class, s.max_per_class, s.dom_boost)


def test_sizes_and_class_counts_match_the_source(pair):
    src, fleet = pair
    np.testing.assert_array_equal(fleet.sizes(), src.sizes)
    np.testing.assert_array_equal(fleet.class_counts(0, M), src.class_counts_block(0, M))


def test_shards_match_the_source(pair):
    src, fleet = pair
    for cid in np.random.default_rng(0).choice(M, 64, replace=False):
        mine, theirs = fleet.shard(cid), src.shard(cid)
        np.testing.assert_array_equal(mine.x, theirs.x)
        np.testing.assert_array_equal(mine.y, theirs.y)


@pytest.mark.parametrize("m,size", [(M, 64), (1_000_000, 2048), (50, 50)])
def test_uniform_cohort_matches_cohort_spec(m, size):
    from repro.federated.sampling import CohortSpec

    spec = CohortSpec(size, "uniform", seed=SEED)
    for b, er in [(1, 1), (1, 2), (3, 2)]:
        np.testing.assert_array_equal(gen_fleet.uniform_cohort(SEED, size, m, b, er),
                                      spec.draw(b, er, eligible=None, m=m))

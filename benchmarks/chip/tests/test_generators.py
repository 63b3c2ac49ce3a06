"""The benchmark's own generator against the program's: the plain
reference trains on the former, the engine on the latter."""
import numpy as np

import gen


def test_table3_population_matches_build_scenario():
    from repro.federated import build_scenario

    sc = build_scenario("heartbeat", scale=0.02, seed=7, n_test_per_class=10)
    shards, counts = gen.table3_population(7, 0.02)
    np.testing.assert_array_equal(counts, sc.class_counts)
    for mine, theirs in zip(shards, sc.clients):
        np.testing.assert_array_equal(mine.x, theirs.shard.x)
        np.testing.assert_array_equal(mine.y, theirs.shard.y)

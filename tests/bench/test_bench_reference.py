"""The yardstick's own graph code: its copies of the generator, the walks
and the host BFS agree with the program's originals and with the dense
closure oracle, and the edge log replays versions as the index does."""
import numpy as np
import pytest

from bench import reference as R
from tests.conftest import random_graph, reach_oracle


def test_host_reach_matches_the_closure_oracle():
    rng = np.random.default_rng(5)
    for _ in range(20):
        n, src, dst = random_graph(rng, n_max=40, m_max=120)
        want = reach_oracle(n, src, dst)
        us = rng.integers(0, n, 150).astype(np.int32)
        ws = rng.integers(0, n, 150).astype(np.int32)
        np.testing.assert_array_equal(R.host_reach(src, dst, n, us, ws),
                                      want[us, ws])


def test_walk_targets_are_reachable():
    rng = np.random.default_rng(6)
    n, src, dst = random_graph(rng, n_max=40, m_max=120)
    us = rng.integers(0, n, 200).astype(np.int32)
    ws = R.walk_targets(src, dst, n, us, rng)
    assert reach_oracle(n, src, dst)[us, ws].all()


@pytest.mark.parametrize("back_frac", [0.02, 0.05])
def test_dag_like_copy_matches_the_program(back_frac):
    from repro.graphs.generators import dag_like
    for seed in (0, 7, 2 ** 40 + 3):
        a = R.dag_like(5000, 9000, seed=seed, back_frac=back_frac)
        b = dag_like(5000, 9000, seed=seed, back_frac=back_frac)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])


def test_edge_log_replays_versions():
    log = R.EdgeLog(4, [0, 1], [1, 2])
    v1 = log.insert(np.array([2]), np.array([3]))
    v2 = log.delete(np.array([1]), np.array([2]))
    v3 = log.insert(np.array([1]), np.array([2]))
    assert (v1, v2, v3) == (1, 2, 3)
    us, ws = np.array([0, 0, 1]), np.array([3, 2, 2])
    np.testing.assert_array_equal(log.reach(0, us, ws), [False, True, True])
    np.testing.assert_array_equal(log.reach(1, us, ws), [True, True, True])
    np.testing.assert_array_equal(log.reach(2, us, ws), [False, False, False])
    np.testing.assert_array_equal(log.reach(3, us, ws), [True, True, True])

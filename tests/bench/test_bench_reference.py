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
    ws = R.walk_targets(*R.csr(src, dst, n), us, rng)
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


def test_out_csr_is_the_csr_of_the_live_graph():
    """The edge log's kept order gives what ``csr`` of a snapshot does,
    through inserts (also of edges it already holds) and deletes."""
    rng = np.random.default_rng(8)
    n = 50
    log = R.EdgeLog(n, rng.integers(0, n, 300), rng.integers(0, n, 300))
    for step in range(12):
        indptr, heads = log.out_csr()
        want = R.csr(*log.snapshot(), n)
        np.testing.assert_array_equal(indptr, want[0])
        np.testing.assert_array_equal(heads, want[1])
        if step % 3 == 2:
            src, dst = log.snapshot()
            pick = rng.choice(src.size, 20, replace=False)
            log.delete(src[pick], dst[pick])
        else:
            s = rng.integers(0, n, 40)
            log.insert(s, rng.integers(0, n, 40))


@pytest.mark.parametrize("n,m", [(3000, 6000), (265000, 420000)])
def test_bow_tie_has_the_configured_components(n, m):
    """``bench/graphs/bow_tie.py`` at the tiny and the full size of
    ``email-euall``: m distinct edges without self-loops, its largest SCC
    and WCC hold exactly the configured shares of vertices and edges, and
    the degrees are heavy-tailed."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components
    from bench import harness as H
    cfg = dict(H.load_json(H.ROOT / "bench/configs/email-euall.json"),
               n=n, m=m)
    g = cfg["generator"]
    src, dst = H.graph_generator(cfg).make(cfg, 11)
    assert src.size == m and not (src == dst).any()
    assert np.unique(src.astype(np.int64) * n + dst).size == m
    a = csr_matrix((np.ones(m), (src, dst)), shape=(n, n))
    for kind, share in (("strong", "scc"), ("weak", "wcc")):
        _, label = connected_components(a, connection=kind)
        big = label == np.bincount(label).argmax()
        assert big.sum() == round(g[f"{share}_vertex_share"] * n)
        assert (big[src] & big[dst]).sum() == round(g[f"{share}_edge_share"]
                                                    * m)
    assert np.bincount(src).max() > 50 * m / n
    assert np.bincount(dst).max() > 20 * m / n

"""Level counts of the BFS residue and round counts of the label fixpoints.

``pruned_bfs(..., counted=True)`` returns, beside the same hits, the level
at which each lane hit and the loop's level count; the engine sums them
(``EngineStats.bfs_*``) from the one host transfer of a flush, and the
insert and rebuild fixpoints' rounds into ``insert_rounds`` and
``rebuild_rounds``.  The counts are checked against host BFSes written
here, not against the program's own loop."""
from collections import deque

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DBLIndex, make_graph
from repro.core import query as Q
from repro.core import update as U
from repro.core.graph import edge_mask
from repro.serve.engine import QueryEngine

N = 48
MAX_ITERS = 48


def _graph(seed, n=N, m=100, m_cap=256):
    """Short forward edges and a tenth of random ones, with two landmarks
    and two leaf buckets: the labels leave about a third of random pairs
    to the BFS, a third of those reachable, some many levels deep."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n - 1, m).astype(np.int32)
    dst = np.minimum(src + rng.integers(1, 6, m), n - 1).astype(np.int32)
    jump = rng.random(m) < 0.1
    dst[jump] = rng.integers(0, n, int(jump.sum()))
    g = make_graph(src, dst, n, m_cap=m_cap)
    return g, DBLIndex.build(g, n_cap=n, k=2, k_prime=2, max_iters=MAX_ITERS)


def _residue_lanes(idx, rng, q):
    """(u, v) of ``q`` lanes the labels leave unknown (the BFS residue),
    the last few of them dead (source ``n_cap``, as the engine pads)."""
    u = rng.integers(0, N, 20000).astype(np.int32)
    v = rng.integers(0, N, 20000).astype(np.int32)
    verd = np.asarray(idx.label_verdicts(u, v))
    keep = np.flatnonzero(verd == -1)[:q - 3]
    assert keep.size >= q // 2, "too few residue lanes to test"
    pad = q - keep.size
    return (np.concatenate([u[keep], np.full(pad, N, np.int32)]),
            np.concatenate([v[keep], np.zeros(pad, np.int32)]))


def _edges(g, m_cut_lane):
    """Edges of one lane: live, and below its edge-count cutoff."""
    live = np.asarray(edge_mask(g))
    ids = np.arange(live.size)
    keep = live & (ids < m_cut_lane)
    return np.asarray(g.src)[keep], np.asarray(g.dst)[keep]


def _distance(src, dst, u, v):
    """Shortest-path length from u to v (None if v is unreachable)."""
    adj = [[] for _ in range(N)]
    for a, b in zip(src, dst):
        adj[a].append(b)
    seen = {u: 0}
    todo = deque([u])
    while todo:
        x = todo.popleft()
        for y in adj[x]:
            if y not in seen:
                seen[y] = seen[x] + 1
                if y == v:
                    return seen[y]
                todo.append(y)
    return None


def _loop_levels(g, admit, u, v, cuts, max_iters):
    """Level count of a lane-parallel BFS run as the program runs it: each
    level relaxes every lane's frontier once; a lane stops at its hit; the
    loop stops when no lane has a frontier, every lane hit, or at
    ``max_iters``."""
    lanes = []
    for q in range(u.size):
        s, d = _edges(g, cuts[q])
        adj = [[] for _ in range(N)]
        for a, b in zip(s, d):
            adj[a].append(b)
        frontier = {int(u[q])} if u[q] < N else set()
        lanes.append(dict(adj=adj, frontier=frontier, seen=set(frontier),
                          hit=False))
    it = 0
    while (any(ln["frontier"] for ln in lanes)
           and not all(ln["hit"] for ln in lanes) and it < max_iters):
        it += 1
        for q, ln in enumerate(lanes):
            nxt = set()
            if not ln["hit"]:
                for x in ln["frontier"]:
                    nxt.update(y for y in ln["adj"][x]
                               if admit[y, q] and y not in ln["seen"])
            ln["seen"] |= nxt
            ln["frontier"] = nxt
            ln["hit"] = ln["hit"] or int(v[q]) in nxt
    return it


@pytest.mark.parametrize("frontier_dtype", ["int8", "int32", "packed"])
@pytest.mark.parametrize("seed,with_cut", [(0, False), (1, True), (2, False),
                                           (3, True)])
def test_hit_level_is_the_shortest_path(seed, with_cut, frontier_dtype):
    """Each reachable residue lane hits at its shortest-path distance over
    the live edges below its cutoff (the prune is sound, so the shortest
    path survives it); an unreachable lane never hits; ``levels`` is the
    loop's level count; the hits are the uncounted form's, bit for bit."""
    g, idx = _graph(seed)
    rng = np.random.default_rng(100 + seed)
    u, v = _residue_lanes(idx, rng, 40)
    m = int(np.asarray(g.m))
    cuts = (rng.integers(m // 2, m + 1, u.size).astype(np.int32)
            if with_cut else np.full(u.size, Q.FRESH_CUT, np.int32))
    kw = dict(m_cut=jnp.asarray(cuts), n_cap=N, max_iters=MAX_ITERS,
              frontier_dtype=frontier_dtype)
    uj, vj = jnp.asarray(u), jnp.asarray(v)
    hits = np.asarray(Q.pruned_bfs(g, idx.packed, uj, vj, None, **kw))
    table = Q.pruned_bfs(g, idx.packed, uj, vj, None, counted=True, **kw)
    assert table.shape == (u.size, 2) and table.dtype == jnp.int32
    hit, hit_level, levels = (np.asarray(x) for x in Q.split_counted(table))
    np.testing.assert_array_equal(hit, hits)
    assert hit.any() and not hit.all()
    for q in range(u.size):
        want = None
        if u[q] < N:
            want = _distance(*_edges(g, cuts[q]), int(u[q]), int(v[q]))
        assert hit_level[q] == (want or 0), (q, want, hit_level[q])
    dl_on = jnp.asarray(cuts >= m)
    admit = np.asarray(Q._admit_plane(idx.packed, uj, vj, N, dl_on))
    assert int(levels) == _loop_levels(g, admit, u, v, cuts, MAX_ITERS)
    assert int(levels) > hit_level.max()     # dead lanes keep it running


def test_level_cap_counts_the_capped_levels():
    """A loop cut off at ``max_iters`` answers its open lanes at that
    level; lanes found within the cap keep their hit levels."""
    g, idx = _graph(5)
    u, v = _residue_lanes(idx, np.random.default_rng(6), 32)
    uj, vj = jnp.asarray(u), jnp.asarray(v)
    full = Q.split_counted(Q.pruned_bfs(g, idx.packed, uj, vj, n_cap=N,
                                        max_iters=MAX_ITERS, counted=True))
    cap = 2
    table = np.asarray(Q.pruned_bfs(g, idx.packed, uj, vj, n_cap=N,
                                    max_iters=cap, counted=True))
    assert int(full[2]) > cap
    want = np.where(np.asarray(full[1]) <= cap, np.asarray(full[1]), 0)
    np.testing.assert_array_equal(table[:, 0], want)
    np.testing.assert_array_equal(
        table[:, 1], np.where(want > 0, want, cap))


def _one_dispatch_engine(seed):
    g, idx = _graph(seed)
    eng = QueryEngine(idx, bfs_chunk=64, max_iters=MAX_ITERS)
    rng = np.random.default_rng(seed)
    u = rng.integers(0, N, 512).astype(np.int32)
    v = rng.integers(0, N, 512).astype(np.int32)
    return g, idx, eng, u, v


def test_engine_sums_the_level_counts_of_its_dispatches():
    """One flush, one dispatch: the engine's four BFS counters are the
    sums the residue's own counted BFS gives, and the answers are the
    reference driver's."""
    g, idx, eng, u, v = _one_dispatch_engine(7)
    (ans,) = eng.flush([eng.submit(idx, u, v)])
    np.testing.assert_array_equal(
        ans, Q.query(g, idx.packed, u, v, n_cap=N, max_iters=MAX_ITERS))
    verd = np.asarray(idx.label_verdicts(u, v))
    res = np.flatnonzero(verd == -1)
    assert 0 < res.size <= 64 and eng.stats.bfs_dispatches == 1
    chunk = eng._bucket_for(res.size)
    uu = np.full(chunk, N, np.int32)
    vv = np.zeros(chunk, np.int32)
    uu[:res.size], vv[:res.size] = u[res], v[res]
    _, hit_level, levels = (np.asarray(x) for x in Q.split_counted(
        Q.pruned_bfs(g, idx.packed, jnp.asarray(uu), jnp.asarray(vv),
                     n_cap=N, max_iters=MAX_ITERS, counted=True)))
    live = hit_level[:res.size]
    s = eng.stats.as_dict()
    assert s["bfs_levels"] == levels > 0
    assert s["bfs_slot_levels"] == levels * chunk
    assert s["bfs_open_lane_levels"] == np.where(live > 0, live,
                                                 levels).sum()
    assert s["bfs_proof_levels"] == levels - live.max()


def test_engine_level_counts_add_up_across_flushes():
    g, idx, eng, u, v = _one_dispatch_engine(8)
    eng.flush([eng.submit(idx, u, v)])
    one = eng.stats.as_dict()
    eng.flush([eng.submit(idx, u, v)])
    two = eng.stats.as_dict()
    for k in ("bfs_levels", "bfs_slot_levels", "bfs_open_lane_levels",
              "bfs_proof_levels", "bfs_dispatches"):
        assert two[k] == 2 * one[k] > 0, k
    assert one["bfs_open_lane_levels"] <= one["bfs_slot_levels"]


def test_insert_and_rebuild_rounds_add_up():
    """``insert_rounds`` gains each insert's fixpoint rounds at the next
    flush (never on the insert path), and ``insert_rounds_batches`` counts
    the inserts it holds; ``rebuild_rounds`` gains each rebuild's."""
    g, idx, eng, u, v = _one_dispatch_engine(9)
    rng = np.random.default_rng(10)
    want = 0
    for _ in range(3):
        ns = rng.integers(0, N, 6).astype(np.int32)
        nd = rng.integers(0, N, 6).astype(np.int32)
        cur = eng.index
        *_, iters, _ = U.insert_and_update(
            cur.graph, cur.dl_in, cur.dl_out, cur.bl_in, cur.bl_out,
            jnp.asarray(ns), jnp.asarray(nd), n_cap=N, max_iters=MAX_ITERS)
        want += int(U.rounds(iters, MAX_ITERS))
        eng.insert(ns, nd)
    assert eng.stats.insert_rounds == 0 and len(eng._sat_flags) == 3
    assert eng.stats.insert_rounds_batches == 0
    eng.flush([eng.submit(eng.index, u, v)])
    assert eng.stats.insert_rounds == want > 0
    assert eng.stats.insert_rounds_batches == 3
    eng.delete(np.asarray(g.src)[:5], np.asarray(g.dst)[:5])
    eng.rebuild(mode="delta")
    first = eng.last_rebuild_info["rounds"]
    eng.delete(np.asarray(g.src)[5:9], np.asarray(g.dst)[5:9])
    eng.rebuild(mode="full")
    second = eng.last_rebuild_info["rounds"]
    assert isinstance(first, int) and isinstance(second, int)
    assert first > 0 and second > 0
    assert eng.stats.rebuild_rounds == first + second


def test_rounds_count_a_cut_off_fixpoint_at_its_cap():
    iters = jnp.asarray([3, 9, 6], jnp.int32)     # 9 = cut off at 8
    assert int(U.rounds(iters, 8)) == 3 + 8 + 6

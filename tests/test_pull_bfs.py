"""The pull form of the BFS residue (``core.query.pulled_bfs`` over a
``core.pull.InEdgeLayout``) against the push form it replaces in the
engine: ``pruned_bfs(..., counted=True)`` without a layout is the oracle,
and the two level tables must be equal bit for bit — the same hits, hit
levels and level count — under every cutoff, tombstone, prune and tail
the engine can hand the residue."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DBLIndex, make_graph
from repro.core import pull as P
from repro.core import query as Q
from repro.core.graph import delete_edges, edge_mask, insert_edges

N = 48
M = 110
M_CAP = 256
MAX_ITERS = 48
HUB = N - 1            # gains 20 in-edges: the widest class (24)
LONE = (0, 1)          # never the head of an edge: in-degree 0


def _graph(seed, families=("dl", "bl")):
    """Short forward edges, a tenth of random ones and a hub; vertices 0
    and 1 are only ever sources."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N - 1, M).astype(np.int32)
    dst = np.minimum(src + rng.integers(1, 6, M), N - 2).astype(np.int32)
    jump = rng.random(M) < 0.1
    dst[jump] = rng.integers(2, N - 1, int(jump.sum()))
    dst[:20] = HUB
    dst[np.isin(dst, LONE)] = 2
    g = make_graph(src, dst, N, m_cap=M_CAP)
    kw = dict(il_dim=2, il_seed=3) if "il" in families else {}
    idx = DBLIndex.build(g, n_cap=N, k=2, k_prime=2, max_iters=MAX_ITERS,
                         families=families, **kw)
    return idx


def _build(g, m=None, like=None):
    """``core.pull.build`` of the first ``m`` edge slots of ``g`` (all of
    them by default): the layout and its tail's room for inserts."""
    if m is not None:
        g = g._replace(m=jnp.int32(m))
    return P.build(g, int(g.m), N, like=like)


def _layout(g):
    """The layout of every edge of ``g``, with no tail: what the engine
    pulls over while nothing was inserted since the layout."""
    return P.without_tail(_build(g)[0])


def _lanes(rng, q=40):
    """Random lanes, the last four dead (source ``n_cap``), two starting
    at in-degree-0 vertices and two aimed at the hub."""
    u = rng.integers(0, N, q).astype(np.int32)
    v = rng.integers(0, N, q).astype(np.int32)
    u[:2] = LONE
    v[2:4] = HUB
    u[-4:] = N
    return jnp.asarray(u), jnp.asarray(v)


CASES = ("plain", "cut", "dirty", "il", "admit_int8", "tail", "tail_dirty",
         "past_threshold")


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pull_level_table_equals_push(seed, case):
    rng = np.random.default_rng(seed)
    idx = _graph(seed, ("dl", "bl", "il") if case == "il" else ("dl", "bl"))
    g = idx.graph
    lay = _layout(g)
    u, v = _lanes(rng)
    kw = dict(n_cap=N, max_iters=MAX_ITERS, counted=True)
    args = dict(admit=None, m_cut=None, dl_clean=None, il=None)
    if case in ("cut", "tail", "tail_dirty", "past_threshold"):
        cut = rng.integers(M // 2, M + 40, u.shape[0]).astype(np.int32)
        cut[::5] = Q.FRESH_CUT
        args["m_cut"] = jnp.asarray(cut)
    if case == "il":
        args["il"] = idx.il
    if case == "admit_int8":
        args["admit"] = Q._admit_plane(idx.packed, u, v, N).astype(jnp.int8)
    if case in ("tail", "tail_dirty", "past_threshold"):
        # edges appended after the layout ride its tail, in the push form;
        # past the tail's room the engine lays the index out anew, keeping
        # the shapes if the in-degrees still fit them, and must stay exact
        lay, room = _build(g)
        extra = 40 if case == "past_threshold" else 4
        ns = rng.integers(0, N, extra).astype(np.int32)
        nd = rng.integers(2, N, extra).astype(np.int32)
        g = insert_edges(g, jnp.asarray(ns), jnp.asarray(nd))
        assert (extra > room) == (case == "past_threshold")
        if extra > room:
            lay, _ = _build(g, like=lay)
    if case in ("dirty", "tail_dirty"):
        # tombstones since the layout: its slots of dead edges go empty
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        kill = rng.choice(int(g.m), 12, replace=False)
        g = delete_edges(g, jnp.asarray(src[kill]), jnp.asarray(dst[kill]))
        assert int(np.asarray(edge_mask(g)).sum()) < int(g.m)
        args["dl_clean"] = jnp.asarray(False)
    push = np.asarray(Q.pruned_bfs(g, idx.packed, u, v, **args, **kw))
    pull = np.asarray(Q.pruned_bfs(g, idx.packed, u, v, **args, **kw,
                                   layout=lay))
    np.testing.assert_array_equal(pull, push)
    hit = push[:, 0] > 0
    assert hit.any() and not hit.all(), "lanes must both hit and miss"
    assert not hit[-4:].any(), "dead lanes never hit"


@pytest.mark.parametrize("frontier_dtype", ["int8", "int32"])
def test_pull_hits_equal_push_uncounted(frontier_dtype):
    rng = np.random.default_rng(5)
    idx = _graph(5)
    u, v = _lanes(rng)
    kw = dict(n_cap=N, max_iters=MAX_ITERS, frontier_dtype=frontier_dtype)
    push = Q.pruned_bfs(idx.graph, idx.packed, u, v, **kw)
    pull = Q.pruned_bfs(idx.graph, idx.packed, u, v, **kw,
                        layout=_layout(idx.graph))
    np.testing.assert_array_equal(np.asarray(pull), np.asarray(push))


def test_packed_frontier_has_no_pull_form():
    idx = _graph(0)
    u, v = _lanes(np.random.default_rng(0))
    with pytest.raises(ValueError, match="packed"):
        Q.pruned_bfs(idx.graph, idx.packed, u, v, n_cap=N,
                     frontier_dtype="packed", layout=_layout(idx.graph))


@pytest.mark.parametrize("seed", [0, 1])
def test_layout_holds_every_live_edge_once(seed):
    """Each live edge sits in its head's row, once; in-degree-0 vertices
    get no slots; the hub is in the widest class; a row holds at most
    1.5x its vertex's in-degree; ``pos`` and ``inv`` are inverses."""
    g = _graph(seed).graph
    g = delete_edges(g, g.src[30:33], g.dst[30:33])
    lay = _layout(g)
    pos, inv = np.asarray(lay.pos), np.asarray(lay.inv)
    rows = inv.shape[0]
    np.testing.assert_array_equal(inv[pos], np.arange(N))
    assert (inv[np.setdiff1d(np.arange(rows), pos)] == N).all()
    live = np.flatnonzero(np.asarray(edge_mask(g)))
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    seen, off = [], 0
    widths = [t.shape[0] for t in lay.src]
    assert widths == sorted(widths) and widths[-1] == 24
    for st, et in zip(lay.src, lay.eid):
        st, et = np.asarray(st), np.asarray(et)
        w, r = st.shape
        slot = et < M_CAP
        assert (st[~slot] == rows - 1).all(), "padding reads the empty row"
        cols = np.nonzero(slot)[1]
        heads = inv[off + cols]
        np.testing.assert_array_equal(dst[et[slot]], heads)
        np.testing.assert_array_equal(pos[src[et[slot]]], st[slot])
        deg = np.bincount(cols, minlength=r)
        used = deg > 0
        assert (deg[used] * 3 >= 2 * w).all()
        seen.append(et[slot])
        off += r
    seen = np.sort(np.concatenate(seen))
    np.testing.assert_array_equal(seen, live)
    deg = np.bincount(dst[live], minlength=N)
    assert (pos[np.flatnonzero(deg == 0)] >= off).all()
    assert pos[HUB] >= off - lay.src[-1].shape[1]


def test_layout_keeps_its_shapes_when_the_degrees_fit():
    """A re-layout after deletions keeps the class shapes and the tail's
    capacity (so the engine compiles nothing new); one after a hub grows
    past them by more than half the tail does not, and gives its classes
    room to drift further."""
    g = _graph(3).graph
    lay, _ = _build(g)
    shapes = [t.shape for t in lay.src] + [lay.inv.shape, lay.tail.shape]
    g2 = delete_edges(g, g.src[:30], g.dst[:30])
    lay2, room = _build(g2, like=lay)
    assert [t.shape for t in lay2.src] + [lay2.inv.shape,
                                          lay2.tail.shape] == shapes
    assert room == lay.tail.shape[0]
    g3 = insert_edges(g, jnp.full(12, 3, jnp.int32),
                      jnp.full(12, HUB, jnp.int32))
    lay3, _ = _build(g3, like=lay)
    assert lay3.src[-1].shape[0] > lay.src[-1].shape[0]


def test_reshaped_layout_leaves_room_to_drift():
    """When inserts outgrow a layout's shapes, the new shapes give every
    class an eighth more rows than its vertices need (then the granule),
    so the next inserts fit them; a first layout has no such room."""
    n, m = 2000, 4000
    rng = np.random.default_rng(7)
    g = make_graph(rng.integers(0, n, m).astype(np.int32),
                   rng.integers(0, n, m).astype(np.int32), n, m_cap=6000)
    lay, _ = P.build(g, m, n)
    g2 = insert_edges(g, jnp.asarray(rng.integers(0, n, 1500), jnp.int32),
                      jnp.asarray(rng.integers(0, n, 1500), jnp.int32))
    lay2, room = P.build(g2, m + 1500, n, like=lay)
    deg = np.bincount(np.asarray(g2.dst)[np.asarray(edge_mask(g2))],
                      minlength=n)
    widths = [t.shape[0] for t in lay2.src]
    counts = np.bincount(np.searchsorted(widths, deg[deg > 0]),
                         minlength=len(widths))
    gran = P.granule(n)
    assert [t.shape[1] for t in lay2.src] == [
        -(-(c + c // 8) // gran) * gran for c in counts]
    assert [t.shape[1] for t in P.build(g2, m + 1500, n)[0].src] == [
        -(-c // gran) * gran for c in counts]
    assert room == P.tail_capacity(m + 1500, gran)


def test_class_widths_and_tail_capacity():
    assert P.class_widths(15) == [1, 2, 3, 4, 6, 8, 12, 16]
    assert P.class_widths(1) == [1]
    assert [P.tail_capacity(m, 8) for m in (0, 110, 128, 129, 4096)] == \
        [8, 8, 8, 8, 256]
    assert P.tail_capacity(4_150_000, 1024) == 260_096


@pytest.mark.parametrize("seed", [0, 1])
def test_left_over_vertices_ride_the_tail(seed):
    """A re-layout that keeps its older shapes leaves vertices without a
    row when the in-degrees outgrow them: a vertex wider than the widest
    class, and, when a class runs out of rows, its lowest in-degrees.
    Their in-edges lead the tail, the room for inserts shrinks by as
    much, and the pull stays exact."""
    n = 48
    chain = [0] + list(range(10, 18))                  # 0 -> 10 -> ... -> 17
    src = np.asarray(chain[:-1] + [17, 12, 20], np.int32)
    dst = np.asarray(chain[1:] + [20, 20, 21], np.int32)
    rng = np.random.default_rng(seed)
    order = np.concatenate([rng.permutation(8), [8, 9, 10]])
    src, dst = src[order], dst[order]
    g = make_graph(src, dst, n, m_cap=32)
    idx = DBLIndex.build(g, n_cap=n, k=2, k_prime=2, max_iters=MAX_ITERS)
    # laid out over the first 8 edges: one class of width 1, 8 rows
    lay0, room0 = _build(g, m=8)
    assert [t.shape for t in lay0.src] == [(1, 8)] and room0 == 8
    lay, room = _build(g, like=lay0)
    assert [t.shape for t in lay.src] == [(1, 8)]
    assert lay.tail.shape == lay0.tail.shape
    # 20 (in-degree 2) fits no class; 21 takes a row from the lowest id of
    # in-degree 1, vertex 10, whose edge from 0 rides the tail with 20's
    head = np.asarray(dst)[np.asarray(lay.tail)[:3]]
    np.testing.assert_array_equal(np.sort(head), [10, 20, 20])
    assert room == 5
    np.testing.assert_array_equal(np.asarray(lay.tail)[3:], np.arange(11, 16))
    u = jnp.asarray([0, 0, 10, 13, 0, 21, n, 0], jnp.int32)
    v = jnp.asarray([21, 20, 21, 17, 12, 0, 5, 1], jnp.int32)
    cut = jnp.asarray([Q.FRESH_CUT, 10, 11, 9, 11, 11, 11, 11], jnp.int32)
    # every vertex admitted: the lanes walk the graph unpruned
    kw = dict(n_cap=n, max_iters=MAX_ITERS, counted=True, m_cut=cut,
              admit=jnp.ones((n, u.shape[0]), jnp.int8))
    push = np.asarray(Q.pruned_bfs(g, idx.packed, u, v, **kw))
    pull = np.asarray(Q.pruned_bfs(g, idx.packed, u, v, **kw, layout=lay))
    np.testing.assert_array_equal(pull, push)
    assert push[0, 0] == 5 and push[:, 0].min() == 0     # 0-10-11-12-20-21

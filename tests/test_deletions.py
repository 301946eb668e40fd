"""Fully-dynamic differential suite: epoch-versioned tombstones, verdict
downgrade, lazy rebuild.

The invariant under test everywhere: a DIRTY index (tombstones newer than
its labels) must answer queries **bitwise identical** to an index freshly
rebuilt from the live edge set (the "rebuild oracle"), which itself must
equal the dense transitive-closure oracle.  This covers the case
insertion-only DBL never exercises — label bits that certify paths through
deleted edges (including SCC-split cascades) must be neutralized by the
verdict-downgrade rule, not trusted.

Soundness cases pinned here:
- FALSE verdicts stay sound forever (BL containment needs completeness
  only; deletion removes edges, never bits);
- TRUE verdicts downgrade (DL positives / theorem negatives ride the
  live-edge BFS while dirty);
- deletions only shrink reachability (anti-monotone law);
- the engine drains in-flight submits before tombstoning and re-binds on
  rebuild, so every consistency contract from the insert-only suite
  survives the fully-dynamic stream.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import DBLIndex, make_graph
from repro.core import graph as G
from repro.core.dbl import LabelSaturationError, LabelSaturationWarning
from repro.serve.engine import QueryEngine
from repro.serve.reach_server import ReachabilityServer
from tests._hyp import given, settings, st
from tests.conftest import reach_oracle, random_graph


def _all_pairs(n):
    u, v = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    return u.ravel().astype(np.int32), v.ravel().astype(np.int32)


class EdgeMirror:
    """Host-side mirror of the tombstone semantics: a delete of (u, v)
    kills ALL live duplicates of that pair."""

    def __init__(self, src, dst):
        self.edges = list(zip(src.tolist(), dst.tolist()))

    def insert(self, ns, nd):
        self.edges += list(zip(ns.tolist(), nd.tolist()))

    def delete(self, ds, dd):
        kill = set(zip(ds.tolist(), dd.tolist()))
        self.edges = [e for e in self.edges if e not in kill]

    def arrays(self):
        if not self.edges:
            return (np.zeros(0, np.int32), np.zeros(0, np.int32))
        s, d = zip(*self.edges)
        return np.asarray(s, np.int32), np.asarray(d, np.int32)

    def oracle(self, n):
        s, d = self.arrays()
        return reach_oracle(n, s, d)


def _check_vs_rebuild_oracle(idx, mirror, n, *, max_iters):
    """Dirty index == rebuilt-from-live-edges index == dense oracle,
    bitwise, on all pairs, both drivers."""
    u, v = _all_pairs(n)
    R = mirror.oracle(n)
    want = R[u, v]
    got_host = np.asarray(idx.query(u, v, bfs_chunk=16, max_iters=max_iters,
                                    driver="host"))
    np.testing.assert_array_equal(got_host, want,
                                  err_msg="host driver diverged from oracle")
    rebuilt = idx.rebuild(max_iters=max_iters)
    got_reb = np.asarray(rebuilt.query(u, v, bfs_chunk=16,
                                       max_iters=max_iters, driver="host"))
    np.testing.assert_array_equal(
        got_host, got_reb,
        err_msg="tombstone-mode answers diverged from the rebuild oracle")
    assert not rebuilt.is_dirty
    # rebuild compacts: live count drops to the mirror's edge count
    assert int(rebuilt.graph.m) == len(mirror.edges)


# ------------------------------------------------- graph-level tombstones
def test_tombstones_are_epoch_versioned():
    src = np.asarray([0, 1, 0, 2, 0], np.int32)
    dst = np.asarray([1, 2, 1, 3, 4], np.int32)
    g = make_graph(src, dst, 5, m_cap=8)
    g1 = G.delete_edges(g, [0], [1])        # kills BOTH (0,1) duplicates
    assert int(g1.del_epoch) == 1
    live1 = np.asarray(G.edge_mask(g1))
    np.testing.assert_array_equal(live1[:5], [False, True, False, True, True])
    g2 = G.delete_edges(g1, [2], [3])
    assert int(g2.del_epoch) == 2
    # as-of reconstruction: epoch 0 sees everything, epoch 1 sees (2,3)
    np.testing.assert_array_equal(np.asarray(G.edge_mask(g2, 0))[:5],
                                  [True] * 5)
    np.testing.assert_array_equal(np.asarray(G.edge_mask(g2, 1))[:5],
                                  [False, True, False, True, True])
    np.testing.assert_array_equal(np.asarray(G.edge_mask(g2))[:5],
                                  [False, True, False, False, True])
    assert int(G.dead_edge_count(g2)) == 3
    # deleting a pair with no live match: epoch bumps, nothing else changes
    g3 = G.delete_edges(g2, [4], [4])
    assert int(g3.del_epoch) == 3
    np.testing.assert_array_equal(np.asarray(g3.del_at), np.asarray(g2.del_at))


def test_compact_squeezes_tombstones_stably():
    src = np.asarray([0, 1, 2, 3, 4], np.int32)
    dst = np.asarray([1, 2, 3, 4, 0], np.int32)
    g = G.delete_edges(make_graph(src, dst, 5, m_cap=9), [1, 3], [2, 4])
    gc = G.compact(g)
    assert int(gc.m) == 3 and int(gc.del_epoch) == 0
    np.testing.assert_array_equal(np.asarray(gc.src)[:3], [0, 2, 4])
    np.testing.assert_array_equal(np.asarray(gc.dst)[:3], [1, 3, 0])
    assert np.asarray(G.edge_mask(gc))[:3].all()
    # capacity is preserved for future inserts
    assert gc.m_cap == 9
    g2 = G.insert_edges(gc, jnp.asarray([1], jnp.int32),
                        jnp.asarray([3], jnp.int32))
    assert int(g2.m) == 4 and bool(np.asarray(G.edge_mask(g2))[3])


def test_insert_after_delete_reuses_no_slots():
    g = make_graph([0, 1], [1, 2], 3, m_cap=4)
    g = G.delete_edges(g, [0], [1])
    g = G.insert_edges(g, jnp.asarray([2], jnp.int32),
                       jnp.asarray([0], jnp.int32))
    # the tombstoned slot 0 stays dead; the insert appended at slot 2
    np.testing.assert_array_equal(np.asarray(G.edge_mask(g))[:3],
                                  [False, True, True])
    assert int(g.m) == 3


# ------------------------------------- differential: interleaved streams
@given(st.integers(0, 2**31 - 1), st.integers(1, 5))
@settings(max_examples=25, deadline=None)
def test_interleaved_insert_delete_equals_rebuild_oracle(seed, rounds):
    """Random interleavings of insert and delete batches: after EVERY batch
    the dirty index must equal both oracles bitwise on all pairs."""
    rng = np.random.default_rng(seed)
    n, src, dst = random_graph(rng, n_max=14, m_max=36)
    mi = n + 2
    idx = DBLIndex.build(make_graph(src, dst, n, m_cap=len(src) + rounds * 3),
                         n_cap=n, k=min(4, n), k_prime=4, max_iters=mi)
    mirror = EdgeMirror(src, dst)
    for _ in range(rounds):
        if rng.random() < 0.5 and mirror.edges:
            picks = rng.integers(0, len(mirror.edges),
                                 min(3, len(mirror.edges)))
            ds = np.asarray([mirror.edges[i][0] for i in picks], np.int32)
            dd = np.asarray([mirror.edges[i][1] for i in picks], np.int32)
            idx = idx.delete_edges(ds, dd)
            mirror.delete(ds, dd)
        else:
            ns = rng.integers(0, n, 3).astype(np.int32)
            nd = rng.integers(0, n, 3).astype(np.int32)
            idx = idx.insert_edges(ns, nd, max_iters=mi)
            mirror.insert(ns, nd)
        assert not bool(np.asarray(idx.saturated))
        _check_vs_rebuild_oracle(idx, mirror, n, max_iters=mi)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_scc_split_cascade_equals_rebuild_oracle(seed):
    """The case insertion-only DBL never exercises: merge SCCs by inserting
    reversed edges, then DELETE cycle edges so the SCCs split again.  Label
    bits certifying the collapsed component are now stale positives; the
    downgrade rule must neutralize every one of them."""
    rng = np.random.default_rng(seed)
    n, src, dst = random_graph(rng, n_max=12, m_max=30)
    mi = n + 2
    b = min(4, len(src))
    idx = DBLIndex.build(make_graph(src, dst, n, m_cap=len(src) + b),
                         n_cap=n, k=min(4, n), k_prime=4, max_iters=mi)
    mirror = EdgeMirror(src, dst)
    # merge: reversed copies of existing edges close cycles
    picks = rng.integers(0, len(src), b)
    ns = dst[picks].astype(np.int32)
    nd = src[picks].astype(np.int32)
    idx = idx.insert_edges(ns, nd, max_iters=mi)
    mirror.insert(ns, nd)
    _check_vs_rebuild_oracle(idx, mirror, n, max_iters=mi)
    # split: delete the FORWARD edges of those cycles (and their dups)
    ds, dd = src[picks].astype(np.int32), dst[picks].astype(np.int32)
    idx = idx.delete_edges(ds, dd)
    mirror.delete(ds, dd)
    _check_vs_rebuild_oracle(idx, mirror, n, max_iters=mi)
    # and delete the reversed edges too — back below the original graph
    idx = idx.delete_edges(ns, nd)
    mirror.delete(ns, nd)
    _check_vs_rebuild_oracle(idx, mirror, n, max_iters=mi)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_deletion_is_anti_monotone(seed):
    """Deletions only shrink reachability: no pair may flip FALSE -> TRUE."""
    rng = np.random.default_rng(seed)
    n, src, dst = random_graph(rng, n_max=14, m_max=40)
    mi = n + 2
    idx = DBLIndex.build(make_graph(src, dst, n), n_cap=n, k=min(4, n),
                         k_prime=4, max_iters=mi)
    u, v = _all_pairs(n)
    before = np.asarray(idx.query(u, v, bfs_chunk=16, max_iters=mi,
                                  driver="host"))
    picks = rng.integers(0, len(src), min(5, len(src)))
    idx2 = idx.delete_edges(src[picks], dst[picks])
    after = np.asarray(idx2.query(u, v, bfs_chunk=16, max_iters=mi,
                                  driver="host"))
    assert (after <= before).all(), "a deletion made some pair reachable"


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_bl_negatives_stay_sound_while_dirty(seed):
    """The downgrade rule's keep-side: label verdict 0 produced by the dirty
    path must never contradict the live-edge oracle (FALSE-monotone), and
    the dirty path must produce NO positive label verdicts except u == v."""
    from repro.core import query as Q
    rng = np.random.default_rng(seed)
    n, src, dst = random_graph(rng, n_max=14, m_max=40)
    mi = n + 2
    idx = DBLIndex.build(make_graph(src, dst, n), n_cap=n, k=min(4, n),
                         k_prime=4, max_iters=mi)
    picks = rng.integers(0, len(src), min(6, len(src)))
    idx = idx.delete_edges(src[picks], dst[picks])
    mirror = EdgeMirror(src, dst)
    mirror.delete(src[picks], dst[picks])
    u, v = _all_pairs(n)
    verd = np.asarray(Q.dirty_label_verdicts(
        idx.packed, jnp.asarray(u), jnp.asarray(v)))
    R = mirror.oracle(n)
    assert not (verd == 0)[R[u, v]].any(), \
        "dirty BL negative contradicted the live-edge oracle"
    assert ((verd == 1) == (u == v)).all(), \
        "dirty path trusted a non-self label positive"


# ------------------------------------------------------- engine contracts
def _mk(n=48, m=160, m_cap_extra=64, k=4, mi=50, seed=7):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    idx = DBLIndex.build(make_graph(src, dst, n, m_cap=m + m_cap_extra),
                         n_cap=n, k=k, k_prime=k, max_iters=mi)
    return idx, src, dst, rng


def test_engine_delete_drains_inflight_as_of_submit():
    idx, src, dst, rng = _mk()
    eng = QueryEngine(idx, bfs_chunk=32, max_iters=50)
    u = rng.integers(0, 48, 300).astype(np.int32)
    v = rng.integers(0, 48, 300).astype(np.int32)
    pend = eng.submit(eng.index, u, v)
    assert pend._result is None
    eng.delete(src[:20], dst[:20])
    # the delete resolved the pending against the PRE-delete snapshot
    assert pend._result is not None
    R_old = reach_oracle(48, src, dst)
    np.testing.assert_array_equal(pend.resolve(), R_old[u, v])
    assert eng.stats.deletes == 20
    assert eng.index.is_dirty and eng.epoch == 1


def test_engine_dirty_stream_matches_mirror_through_rebuild():
    """Mixed submit/insert/delete stream on the engine, flushing at delete
    boundaries (forced) and at the end; every batch equals its submit-time
    mirror oracle; rebuild() re-binds and clears dirty without changing
    answers."""
    idx, src, dst, rng = _mk()
    n = 48
    eng = QueryEngine(idx, bfs_chunk=32, max_iters=50)
    mirror = EdgeMirror(src, dst)
    pending = []   # (pend, u, v, oracle-at-submit)
    for step in range(6):
        u = rng.integers(0, n, 200).astype(np.int32)
        v = rng.integers(0, n, 200).astype(np.int32)
        pending.append((eng.submit(eng.index, u, v), u, v, mirror.oracle(n)))
        if step % 2 == 0:
            ns = rng.integers(0, n, 8).astype(np.int32)
            nd = rng.integers(0, n, 8).astype(np.int32)
            eng.insert(ns, nd)
            mirror.insert(ns, nd)
        else:
            picks = rng.integers(0, len(mirror.edges), 10)
            ds = np.asarray([mirror.edges[i][0] for i in picks], np.int32)
            dd = np.asarray([mirror.edges[i][1] for i in picks], np.int32)
            eng.delete(ds, dd)    # drains everything submitted so far
            mirror.delete(ds, dd)
    outs = eng.flush([p for p, _, _, _ in pending])
    for (pend, u, v, R), out in zip(pending, outs):
        np.testing.assert_array_equal(out, R[u, v])
    assert eng.index.is_dirty
    # rebuild: in-flight resolved first, dirty cleared, answers unchanged
    u = rng.integers(0, n, 300).astype(np.int32)
    v = rng.integers(0, n, 300).astype(np.int32)
    pend = eng.submit(eng.index, u, v)
    R_live = mirror.oracle(n)
    eng.rebuild()
    assert pend._result is not None
    np.testing.assert_array_equal(pend.resolve(), R_live[u, v])
    assert not eng.index.is_dirty and eng.stats.rebuilds == 1
    np.testing.assert_array_equal(eng.query(u, v), R_live[u, v])


def test_engine_dirty_no_dispatch_shape_churn():
    """Flipping dirty on and off must NOT compile new executables — the
    dirty flag is a traced operand, so the 2-shape budget of the insert-only
    engine survives deletions."""
    idx, src, dst, rng = _mk()
    eng = QueryEngine(idx, bfs_chunk=32, max_iters=50)
    # pre-compile the label shape and BOTH chunk buckets; after this, any
    # new executable can only come from the dirty flag changing a trace
    eng.warmup(idx, batch_sizes=(600,), bfs_buckets=(16, 32))
    u = rng.integers(0, 48, 600).astype(np.int32)
    v = rng.integers(0, 48, 600).astype(np.int32)
    eng.query(u, v)                       # clean pass
    shapes = eng.dispatch_shapes()
    eng.delete(src[:30], dst[:30])
    eng.query(u, v)                       # dirty pass
    eng.rebuild()
    eng.query(u, v)                       # clean again
    eng.delete(src[30:60], dst[30:60])
    eng.query(u, v)                       # dirty again
    assert eng.dispatch_shapes() == shapes, (
        f"dirty flag caused recompilation: {shapes} -> "
        f"{eng.dispatch_shapes()}")


def test_server_lazy_rebuild_policy_at_flush_boundary():
    idx, src, dst, rng = _mk()
    srv = ReachabilityServer(idx, bfs_chunk=32, max_iters=50,
                             rebuild_dead_ratio=0.10)
    n = 48
    mirror = EdgeMirror(src, dst)
    u = rng.integers(0, n, 200).astype(np.int32)
    v = rng.integers(0, n, 200).astype(np.int32)
    srv.submit(u, v)
    R0 = mirror.oracle(n)
    # below threshold: no rebuild scheduled
    srv.delete(src[:2], dst[:2])
    mirror.delete(src[:2], dst[:2])
    assert srv.dirty and not srv._rebuild_due
    # over threshold: scheduled, but NOT executed inside delete()
    srv.delete(src[2:30], dst[2:30])
    mirror.delete(src[2:30], dst[2:30])
    assert srv._rebuild_due and srv.dirty
    outs = srv.flush()                    # resolves, then rebuilds lazily
    np.testing.assert_array_equal(outs[0], R0[u, v])
    assert not srv.dirty and not srv._rebuild_due
    assert srv.stats.rebuilds == 1 and srv.stats.deletes == 30
    np.testing.assert_array_equal(srv.query(u, v), mirror.oracle(n)[u, v])
    es = srv.engine_stats()
    assert es["deletes"] == 30 and es["rebuilds"] == 1 and not es["dirty"]


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_engine_driver_matches_host_on_dirty_index(seed):
    """DBLIndex.query's default engine driver (memoized foreign-index path)
    must honor the dirty state exactly like the host reference driver."""
    rng = np.random.default_rng(seed)
    n, src, dst = random_graph(rng, n_max=14, m_max=40)
    mi = n + 2
    idx = DBLIndex.build(make_graph(src, dst, n), n_cap=n, k=min(4, n),
                         k_prime=4, max_iters=mi)
    picks = rng.integers(0, len(src), min(6, len(src)))
    idx = idx.delete_edges(src[picks], dst[picks])
    u, v = _all_pairs(n)
    host = np.asarray(idx.query(u, v, bfs_chunk=16, max_iters=mi,
                                driver="host"))
    eng = np.asarray(idx.query(u, v, bfs_chunk=16, max_iters=mi,
                               driver="engine"))
    np.testing.assert_array_equal(eng, host)
    mirror = EdgeMirror(src, dst)
    mirror.delete(src[picks], dst[picks])
    np.testing.assert_array_equal(eng, mirror.oracle(n)[u, v])


# -------------------------------------------- satellite: saturation flag
def _path_index(L=12, mi=40, m_cap_extra=4):
    src = np.arange(L - 1, dtype=np.int32)
    dst = np.arange(1, L, dtype=np.int32)
    g = make_graph(src, dst, L, m_cap=len(src) + m_cap_extra)
    return DBLIndex.build(g, n_cap=L, k=2, k_prime=2, max_iters=mi)


def test_insert_saturation_warns_and_sets_flag():
    idx = _path_index()
    assert not bool(np.asarray(idx.saturated))
    # closing the long cycle needs ~L propagation rounds; max_iters=2 can't
    with pytest.warns(LabelSaturationWarning):
        idx2 = idx.insert_edges([11], [0], max_iters=2)
    assert bool(np.asarray(idx2.saturated)), "saturation flag not set"
    # sticky: a later converging insert keeps the flag (labels still stale)
    idx3 = idx2.insert_edges([0], [0], max_iters=40, check="defer")
    assert bool(np.asarray(idx3.saturated))
    # rebuild clears it (fresh labels are exact)
    idx4 = idx3.rebuild(max_iters=40)
    assert not bool(np.asarray(idx4.saturated))


def test_insert_saturation_strict_raises_and_defer_is_silent():
    idx = _path_index()
    with pytest.raises(LabelSaturationError):
        idx.insert_edges([11], [0], max_iters=2, check="raise")
    with pytest.raises(ValueError):
        idx.insert_edges([11], [0], check="sometimes")
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")          # any warning would fail the test
        idx2 = idx.insert_edges([11], [0], max_iters=2, check="defer")
    assert bool(np.asarray(idx2.saturated))
    # a converging insert at sane max_iters warns nothing either
    with _w.catch_warnings():
        _w.simplefilter("error")
        idx.insert_edges([0], [1], max_iters=40)


def test_convergence_at_exact_iteration_limit_is_not_saturation():
    """propagate reports max_iters + 1 only when TRUNCATED: converging in
    exactly max_iters rounds must not warn, raise, or set the flag."""
    from repro.core import update as U
    idx = _path_index(mi=40)
    # measure the rounds this insert actually needs, then re-run with the
    # budget set to exactly that number
    _, _, _, _, _, iters, _ = U.insert_and_update(
        idx.graph, idx.dl_in, idx.dl_out, idx.bl_in, idx.bl_out,
        jnp.asarray([11], jnp.int32), jnp.asarray([0], jnp.int32),
        idx.epoch, n_cap=idx.n_cap, max_iters=40)
    need = int(np.asarray(iters).max())
    assert 2 < need <= 40, need
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        idx2 = idx.insert_edges([11], [0], max_iters=need)
    assert not bool(np.asarray(idx2.saturated))
    # one round fewer IS saturation
    with pytest.warns(LabelSaturationWarning):
        idx3 = idx.insert_edges([11], [0], max_iters=need - 1)
    assert bool(np.asarray(idx3.saturated))


def test_build_and_rebuild_surface_their_own_saturation():
    """A BUILD cut off at max_iters produces incomplete labels too: the
    flag must be set (and warn/raise honored), and rebuild() must not
    launder a saturated rebuild into saturated=False."""
    src = np.arange(11, dtype=np.int32)
    dst = np.arange(1, 12, dtype=np.int32)
    g = make_graph(src, dst, 12, m_cap=14)
    with pytest.warns(LabelSaturationWarning):
        idx = DBLIndex.build(g, n_cap=12, k=2, k_prime=2, max_iters=2)
    assert bool(np.asarray(idx.saturated))
    with pytest.raises(LabelSaturationError):
        DBLIndex.build(g, n_cap=12, k=2, k_prime=2, max_iters=2,
                       check="raise")
    ok = DBLIndex.build(g, n_cap=12, k=2, k_prime=2, max_iters=40)
    assert not bool(np.asarray(ok.saturated))
    with pytest.warns(LabelSaturationWarning):
        reb = ok.delete_edges([0], [1]).rebuild(max_iters=2)
    assert bool(np.asarray(reb.saturated)), \
        "a saturated rebuild must not clear the flag"


def test_engine_defers_saturation_to_flush():
    idx = _path_index()
    eng = QueryEngine(idx, bfs_chunk=16, max_iters=2)
    eng.insert([11], [0])                 # no sync, no warning here
    assert len(eng._sat_flags) == 1
    u = np.zeros(4, np.int32)
    with pytest.warns(LabelSaturationWarning):
        eng.flush([eng.submit(eng.index, u, u)])
    assert eng.stats.saturation_events == 1 and not eng._sat_flags
    assert bool(np.asarray(eng.index.saturated))


# ---------------------------------------- satellite: epoch dtype stability
def test_index_scalar_leaves_are_typed_arrays_everywhere():
    """epoch / label_del_epoch are int32 jax.Arrays and saturated a bool
    jax.Array on EVERY construction path (build, insert, delete, rebuild) —
    a leaf flipping between weak Python int and traced array changes the
    pytree aval and forces jit retraces + checkpoint mismatches."""
    def check(idx, where):
        for name in ("epoch", "label_del_epoch"):
            leaf = getattr(idx, name)
            assert isinstance(leaf, jax.Array), (where, name, type(leaf))
            assert leaf.dtype == jnp.int32, (where, name, leaf.dtype)
            assert not leaf.weak_type, (where, name)
        assert isinstance(idx.saturated, jax.Array), where
        assert idx.saturated.dtype == jnp.bool_, (where, idx.saturated.dtype)
        assert idx.graph.del_epoch.dtype == jnp.int32
        assert idx.graph.del_at.dtype == jnp.int32

    idx, src, dst, rng = _mk(n=16, m=30, mi=20)
    check(idx, "build")
    idx_i = idx.insert_edges([0, 1], [2, 3], max_iters=20)
    check(idx_i, "insert")
    idx_d = idx_i.delete_edges([0], [2])
    check(idx_d, "delete")
    idx_r = idx_d.rebuild(max_iters=20)
    check(idx_r, "rebuild")

    # identical treedef AND leaf avals across the whole lifecycle => one
    # compiled executable serves every stage (no retraces)
    def avals(i):
        return [(l.shape, l.dtype, l.weak_type)
                for l in jax.tree_util.tree_leaves(i)]
    t0 = jax.tree_util.tree_structure(idx)
    for other in (idx_i, idx_d, idx_r):
        assert jax.tree_util.tree_structure(other) == t0
        assert avals(other) == avals(idx)

    calls = 0

    @jax.jit
    def touch(i):
        nonlocal calls
        calls += 1
        return i.epoch + i.graph.m

    for i in (idx, idx_i, idx_d, idx_r):
        touch(i)
    assert calls == 1, f"index lifecycle caused {calls - 1} jit retraces"


def test_distributed_epoch_is_int32_array():
    from repro.core import distributed as D
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((1,), ("data",))
    idx, src, dst, rng = _mk(n=16, m=30, mi=20)
    sharded = D.shard_index(idx, mesh)
    assert sharded.epoch.dtype == jnp.int32 and not sharded.epoch.weak_type
    built = D.distributed_build(idx.graph, mesh, n_cap=16, k=4, k_prime=4,
                                max_iters=20)
    assert built.epoch.dtype == jnp.int32 and not built.epoch.weak_type
    ins = D.distributed_insert(built, mesh, [0], [1], max_iters=20)
    assert ins.epoch.dtype == jnp.int32 and int(ins.epoch) == 1
    assert ins.dl_in.sharding == D.index_shardings(mesh).dl_in

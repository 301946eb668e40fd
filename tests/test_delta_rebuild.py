"""Rebuild-equivalence differential suite for the incremental (delta)
rebuild.

The invariant under test everywhere: ``rebuild(mode="delta")`` must produce
an index whose DL/BL label planes (and packed words, landmark vector, leaf
seed masks, compacted graph) are **bitwise equal** to ``rebuild(mode="full")``
on a cloned index — across property-based streams of interleaved inserts and
deletes, SCC merge-then-split cascades, and landmark/leaf membership churn.
The delta path must also surface its own fixpoint saturation exactly like a
full build (no laundering stale labels into ``saturated=False``), and the
server's lazy-rebuild policy must trigger off the live edge count.

Failure notes carry the ``HYP_SEED`` repro breadcrumb via ``tests._hyp``.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DBLIndex, make_graph
from repro.core.dbl import LabelSaturationError, LabelSaturationWarning
from repro.serve.engine import QueryEngine
from repro.serve.reach_server import ReachabilityServer
from tests._hyp import given, settings, st
from tests.conftest import reach_oracle, random_graph


def _all_pairs(n):
    u, v = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    return u.ravel().astype(np.int32), v.ravel().astype(np.int32)


class Mirror:
    """Host-side mirror of the tombstone semantics (a delete of (u, v)
    kills ALL live duplicates of that pair)."""

    def __init__(self, src, dst):
        self.edges = list(zip(src.tolist(), dst.tolist()))

    def insert(self, ns, nd):
        self.edges += list(zip(ns.tolist(), nd.tolist()))

    def delete(self, ds, dd):
        kill = set(zip(ds.tolist(), dd.tolist()))
        self.edges = [e for e in self.edges if e not in kill]

    def oracle(self, n):
        if not self.edges:
            return reach_oracle(n, np.zeros(0, np.int32), np.zeros(0, np.int32))
        s, d = zip(*self.edges)
        return reach_oracle(n, np.asarray(s, np.int32), np.asarray(d, np.int32))


def assert_rebuild_equal(delta: DBLIndex, full: DBLIndex, tag: str = ""):
    """Delta and full rebuilds must be indistinguishable, leaf for leaf."""
    for name in ("dl_in", "dl_out", "bl_in", "bl_out"):
        np.testing.assert_array_equal(
            np.asarray(getattr(delta, name)), np.asarray(getattr(full, name)),
            err_msg=f"{tag}: {name} diverged from the full-rebuild oracle")
    for w, (dw, fw) in enumerate(zip(delta.packed, full.packed)):
        np.testing.assert_array_equal(np.asarray(dw), np.asarray(fw),
                                      err_msg=f"{tag}: packed word plane {w}")
    np.testing.assert_array_equal(np.asarray(delta.landmarks),
                                  np.asarray(full.landmarks),
                                  err_msg=f"{tag}: landmark vector")
    np.testing.assert_array_equal(np.asarray(delta.bl_sources),
                                  np.asarray(full.bl_sources),
                                  err_msg=f"{tag}: bl_sources")
    np.testing.assert_array_equal(np.asarray(delta.bl_sinks),
                                  np.asarray(full.bl_sinks),
                                  err_msg=f"{tag}: bl_sinks")
    # both compact: identical stable edge order, live count, reset clocks
    assert int(delta.graph.m) == int(full.graph.m), tag
    np.testing.assert_array_equal(np.asarray(delta.graph.src),
                                  np.asarray(full.graph.src), err_msg=tag)
    np.testing.assert_array_equal(np.asarray(delta.graph.dst),
                                  np.asarray(full.graph.dst), err_msg=tag)
    assert int(delta.epoch) == int(full.epoch), tag
    assert int(delta.label_del_epoch) == int(full.label_del_epoch), tag
    assert bool(np.asarray(delta.saturated)) == bool(np.asarray(full.saturated))
    assert not delta.is_dirty and not full.is_dirty


# --------------------------------------- property-based differential streams
@given(st.integers(0, 2**31 - 1), st.integers(1, 4))
@settings(max_examples=15, deadline=None)
def test_delta_equals_full_across_interleaved_streams(seed, rounds):
    """Random interleavings of insert and delete batches: after EVERY batch
    a delta rebuild must equal a full rebuild bitwise, the delta-rebuilt
    index must answer the dense oracle exactly, and the stream CONTINUES
    from the delta index so delta-upon-delta compounding is exercised."""
    rng = np.random.default_rng(seed)
    n, src, dst = random_graph(rng, n_max=14, m_max=36)
    mi = n + 2
    idx = DBLIndex.build(make_graph(src, dst, n, m_cap=len(src) + rounds * 3),
                         n_cap=n, k=min(4, n), k_prime=4, max_iters=mi)
    mirror = Mirror(src, dst)
    u, v = _all_pairs(n)
    for r in range(rounds):
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
        full = idx.rebuild(mode="full", max_iters=mi)
        delta, info = idx.rebuild_info(mode="delta", max_iters=mi)
        assert info["mode"] == "delta", info
        assert_rebuild_equal(delta, full, f"round {r}")
        got = np.asarray(delta.query(u, v, bfs_chunk=16, max_iters=mi,
                                     driver="host"))
        np.testing.assert_array_equal(
            got, mirror.oracle(n)[u, v],
            err_msg=f"round {r}: delta-rebuilt index diverged from oracle")
        idx = delta                      # compound: next round starts here


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=12, deadline=None)
def test_delta_equals_full_on_scc_merge_then_split(seed):
    """Merge SCCs by inserting reversed edges, then DELETE the forward (and
    later the reversed) cycle edges so the SCCs split again — the label
    state delta rebuild must repair includes bits that certified the
    collapsed component."""
    rng = np.random.default_rng(seed)
    n, src, dst = random_graph(rng, n_max=12, m_max=30)
    mi = n + 2
    b = min(4, len(src))
    idx = DBLIndex.build(make_graph(src, dst, n, m_cap=len(src) + b),
                         n_cap=n, k=min(4, n), k_prime=4, max_iters=mi)
    picks = rng.integers(0, len(src), b)
    ns, nd = dst[picks].astype(np.int32), src[picks].astype(np.int32)
    idx = idx.insert_edges(ns, nd, max_iters=mi)     # merge
    for tag, (ds, dd) in (
            ("merge", (None, None)),
            ("split-forward", (src[picks].astype(np.int32),
                               dst[picks].astype(np.int32))),
            ("split-reversed", (ns, nd))):
        if ds is not None:
            idx = idx.delete_edges(ds, dd)
        full = idx.rebuild(mode="full", max_iters=mi)
        delta = idx.rebuild(mode="delta", max_iters=mi)
        assert_rebuild_equal(delta, full, tag)
        idx = delta


def test_delta_handles_landmark_and_leaf_membership_churn():
    """Deterministic churn: delete every edge of the top landmark so it
    falls out of the top-k AND five vertices become fresh source/sink
    leaves — delta must re-select, realign surviving lanes by identity,
    rebuild fresh lanes/buckets from scratch, and still equal full."""
    hub = [(0, i) for i in range(1, 6)] + [(i, 0) for i in range(1, 6)]
    second = [(6, 7), (7, 6), (6, 8), (8, 6)]
    edges = np.asarray(hub + second, np.int32)
    n, mi = 9, 12
    idx = DBLIndex.build(make_graph(edges[:, 0], edges[:, 1], n, m_cap=20),
                         n_cap=n, k=2, k_prime=4, max_iters=mi)
    old_lm = set(np.asarray(idx.landmarks).tolist())
    assert 0 in old_lm                   # the hub is a landmark at build
    ds = np.asarray([e[0] for e in hub], np.int32)
    dd = np.asarray([e[1] for e in hub], np.int32)
    idx = idx.delete_edges(ds, dd)
    full = idx.rebuild(mode="full", max_iters=mi)
    delta = idx.rebuild(mode="delta", max_iters=mi)
    new_lm = set(np.asarray(full.landmarks).tolist())
    assert new_lm != old_lm, "scenario failed to churn the landmark set"
    assert np.asarray(full.bl_sources).sum() > np.asarray(idx.bl_sources).sum()
    assert_rebuild_equal(delta, full, "landmark/leaf churn")


def test_delta_equals_full_after_insert_only_churn():
    """Zero tombstones, but inserts changed the centrality ranking and leaf
    membership since build: a full rebuild re-seeds from the CURRENT graph,
    so the delta path must repair pure seed churn too."""
    src = np.asarray([0, 1, 2, 3], np.int32)
    dst = np.asarray([1, 2, 3, 4], np.int32)
    n, mi = 8, 12
    idx = DBLIndex.build(make_graph(src, dst, n, m_cap=16), n_cap=n, k=2,
                         k_prime=4, max_iters=mi)
    # vertex 5 becomes the dominant hub; vertex 0 stops being a source leaf
    ns = np.asarray([5, 5, 5, 6, 7, 6], np.int32)
    nd = np.asarray([6, 7, 0, 5, 5, 0], np.int32)
    idx = idx.insert_edges(ns, nd, max_iters=mi)
    assert not idx.is_dirty
    full = idx.rebuild(mode="full", max_iters=mi)
    delta = idx.rebuild(mode="delta", max_iters=mi)
    assert_rebuild_equal(delta, full, "insert-only churn")


def test_delta_noop_on_clean_unchurned_index_keeps_labels():
    """No deletions, no seed churn: the delta plan has an empty frontier and
    the labels come through untouched — still equal to a full rebuild."""
    src = np.asarray([0, 1, 2], np.int32)
    dst = np.asarray([1, 2, 3], np.int32)
    idx = DBLIndex.build(make_graph(src, dst, 4, m_cap=8), n_cap=4, k=2,
                         k_prime=2, max_iters=10)
    delta, info = idx.rebuild_info(mode="delta", max_iters=10)
    assert info["estimate"]["frac"] == 0.0
    assert_rebuild_equal(delta, idx.rebuild(mode="full", max_iters=10),
                         "clean noop")
    np.testing.assert_array_equal(np.asarray(delta.dl_in),
                                  np.asarray(idx.dl_in))


# ------------------------------------------- closure backend equivalence
@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_reach_mask_matches_host_closure(seed):
    """The device invalidation closure (``propagate.reach_mask``, used on
    accelerator backends) and the host BFS twin the CPU plan uses must
    agree exactly — including seeds-on-dead-edges and empty seed sets."""
    from repro.core import graph as G_
    from repro.core.dbl import _host_reach
    from repro.core.propagate import reach_mask
    rng = np.random.default_rng(seed)
    n, src, dst = random_graph(rng, n_max=16, m_max=40)
    g = make_graph(src, dst, n, m_cap=len(src) + 4)
    if len(src) > 2:
        g = G_.delete_edges(g, src[:2], dst[:2])
    live = G_.edge_mask(g)
    seeds = rng.random(n) < 0.2
    for reverse in (False, True):
        s_np, d_np = np.asarray(g.src), np.asarray(g.dst)
        if reverse:
            s_np, d_np = d_np, s_np
        host = _host_reach(s_np, d_np, np.asarray(live), seeds)
        dev, iters = reach_mask(g.src, g.dst, live, jnp.asarray(seeds),
                                n_cap=n, max_iters=n, reverse=reverse)
        np.testing.assert_array_equal(np.asarray(dev), host,
                                      err_msg=f"reverse={reverse}")
        assert int(np.asarray(iters)) <= n, "closure reported truncation"


# ------------------------------------------------------- auto-mode policy
def test_auto_mode_picks_delta_or_full_by_invalidation_estimate():
    src = np.arange(9, dtype=np.int32)
    dst = np.arange(1, 10, dtype=np.int32)
    idx = DBLIndex.build(make_graph(src, dst, 10, m_cap=12), n_cap=10, k=2,
                         k_prime=4, max_iters=14)
    idx = idx.delete_edges([8], [9])     # tail of the chain: tiny closure
    _, info_lo = idx.rebuild_info(mode="auto", max_iters=14,
                                  delta_threshold=1.0)
    assert info_lo["mode"] == "delta" and info_lo["reason"] == "estimate"
    assert 0.0 < info_lo["estimate"]["frac"] <= 1.0
    _, info_hi = idx.rebuild_info(mode="auto", max_iters=14,
                                  delta_threshold=0.0)
    assert info_hi["mode"] == "full" and info_hi["reason"] == "estimate"
    # the default threshold is permissive (delta wins under broad
    # invalidation too — see BENCH_PR4) but still routes the degenerate
    # everything-invalidated case to full: deleting every edge churns
    # every leaf bucket, so the estimate hits 1.0
    idx2 = DBLIndex.build(make_graph(src, dst, 10, m_cap=12), n_cap=10, k=2,
                          k_prime=4, max_iters=14).delete_edges(src, dst)
    _, info_all = idx2.rebuild_info(mode="auto", max_iters=14)
    assert info_all["mode"] == "full"
    assert info_all["estimate"]["frac"] > 0.99
    # ... while a broad-but-partial invalidation (head-of-chain deletion)
    # stays on the delta path under the default threshold
    idx3 = DBLIndex.build(make_graph(src, dst, 10, m_cap=12), n_cap=10, k=2,
                          k_prime=4, max_iters=14).delete_edges([0], [1])
    _, info_head = idx3.rebuild_info(mode="auto", max_iters=14)
    assert info_head["mode"] == "delta"
    assert info_head["estimate"]["frac"] > 0.5


# ------------------------------------------------- saturation regressions
def _chain_index(L=12, mi=40, m_cap_extra=4):
    src = np.arange(L - 1, dtype=np.int32)
    dst = np.arange(1, L, dtype=np.int32)
    g = make_graph(src, dst, L, m_cap=len(src) + m_cap_extra)
    return DBLIndex.build(g, n_cap=L, k=2, k_prime=2, max_iters=mi)


def test_delta_fixpoint_truncation_sets_sticky_flag_like_full():
    """A delta rebuild whose frontier fixpoint is cut off at max_iters must
    set ``saturated`` exactly like a truncated full build, for all of
    check="warn"/"raise"/"defer" — no laundering stale labels into
    saturated=False."""
    idx = _chain_index().delete_edges([0], [1])   # closure = the whole tail
    with pytest.warns(LabelSaturationWarning):
        reb = idx.rebuild(mode="delta", max_iters=2)
    assert bool(np.asarray(reb.saturated)), \
        "truncated delta fixpoint must leave the sticky flag set"
    with pytest.warns(LabelSaturationWarning):
        reb_full = idx.rebuild(mode="full", max_iters=2)
    assert bool(np.asarray(reb.saturated)) == bool(np.asarray(reb_full.saturated))
    with pytest.raises(LabelSaturationError):
        idx.rebuild(mode="delta", max_iters=2, check="raise")
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")          # any warning would fail the test
        reb_defer = idx.rebuild(mode="delta", max_iters=2, check="defer")
    assert bool(np.asarray(reb_defer.saturated))
    with pytest.raises(ValueError):
        idx.rebuild(mode="delta", max_iters=2, check="sometimes")
    # adequate budget: converges, flag honestly clear, equal to full
    reb_ok = idx.rebuild(mode="delta", max_iters=40)
    assert not bool(np.asarray(reb_ok.saturated))
    assert_rebuild_equal(reb_ok, idx.rebuild(mode="full", max_iters=40))


def test_delta_on_saturated_index_falls_back_to_full():
    """Stale (saturated) labels are not a sound delta base: even a FORCED
    delta must run the full path — a delta that reused the truncated clean
    region could launder missing bits into saturated=False."""
    with pytest.warns(LabelSaturationWarning):
        idx = _chain_index(mi=2)          # truncated BUILD: saturated
    assert bool(np.asarray(idx.saturated))
    idx = idx.delete_edges([5], [6])
    reb, info = idx.rebuild_info(mode="delta", max_iters=40)
    rounds = info.pop("rounds")        # on the device: no wait here
    assert info == {"mode": "full", "reason": "saturated"}
    # four plane fixpoints, each converged
    assert rounds.shape == () and 0 < int(rounds) <= 4 * 40
    assert not bool(np.asarray(reb.saturated))   # honest full reconvergence
    assert_rebuild_equal(reb, idx.rebuild(mode="full", max_iters=40))
    _, info_auto = idx.rebuild_info(mode="auto", max_iters=40)
    assert info_auto["reason"] == "saturated"


def test_invalid_rebuild_mode_rejected():
    idx = _chain_index()
    with pytest.raises(ValueError):
        idx.rebuild(mode="incremental")


# -------------------------------------------------------- engine contracts
def test_engine_delta_rebuild_rebinds_without_dispatch_shape_churn():
    """A delta rebuild keeps every array shape, so the engine re-bind must
    compile nothing new; the delta counter and info surface the path."""
    rng = np.random.default_rng(7)
    n = 48
    src = rng.integers(0, n, 160).astype(np.int32)
    dst = rng.integers(0, n, 160).astype(np.int32)
    idx = DBLIndex.build(make_graph(src, dst, n, m_cap=224), n_cap=n, k=4,
                         k_prime=4, max_iters=50)
    eng = QueryEngine(idx, bfs_chunk=32, max_iters=50)
    eng.warmup(idx, batch_sizes=(600,), bfs_buckets=(16, 32))
    u = rng.integers(0, n, 600).astype(np.int32)
    v = rng.integers(0, n, 600).astype(np.int32)
    eng.query(u, v)
    shapes = eng.dispatch_shapes()
    mirror = Mirror(src, dst)
    eng.delete(src[:10], dst[:10])
    mirror.delete(src[:10], dst[:10])
    eng.rebuild(mode="delta")
    assert eng.last_rebuild_info["mode"] == "delta"
    assert eng.stats.delta_rebuilds == 1 and eng.stats.rebuilds == 1
    assert not eng.index.is_dirty
    np.testing.assert_array_equal(eng.query(u, v), mirror.oracle(n)[u, v])
    assert eng.dispatch_shapes() == shapes, (
        f"delta rebuild re-bind caused recompilation: {shapes} -> "
        f"{eng.dispatch_shapes()}")


# --------------------------------------------- server lazy-rebuild policy
def _distinct_pair_server(n=40, m0=100, ratio=0.25, seed=3):
    rng = np.random.default_rng(seed)
    flat = rng.choice(n * n - n, size=m0, replace=False)
    u = (flat // (n - 1)).astype(np.int32)
    r = (flat % (n - 1)).astype(np.int32)
    v = np.where(r >= u, r + 1, r).astype(np.int32)   # distinct, no loops
    idx = DBLIndex.build(make_graph(u, v, n, m_cap=m0 + 32), n_cap=n, k=4,
                         k_prime=4, max_iters=50)
    srv = ReachabilityServer(idx, bfs_chunk=32, max_iters=50,
                             rebuild_dead_ratio=ratio)
    return srv, u, v


def test_server_dead_ratio_counts_tombstones_against_live_count():
    """Policy trigger point, pinned: with 100 distinct live edges and
    ratio 0.25, the 20th tombstone crosses dead/live = 20/80 = 0.25.  The
    old denominator (the raw edge prefix m, which includes the tombstones
    themselves) would not have fired until the 25th — and would drift
    further as the dirty window grew."""
    srv, u, v = _distinct_pair_server()
    srv.delete(u[:19], v[:19])            # 19/81 = 0.2346 < 0.25
    assert srv.dirty and not srv._rebuild_due
    srv.delete(u[19:20], v[19:20])        # 20/80 = 0.25 -> due (not executed)
    assert srv._rebuild_due and srv.dirty
    assert srv.stats.rebuilds == 0
    srv.query(np.zeros(4, np.int32), np.zeros(4, np.int32))
    assert srv.stats.rebuilds == 1 and not srv.dirty and not srv._rebuild_due


def test_server_policy_trigger_does_not_drift_after_compact():
    """After the rebuild compacts the 20 tombstones away, a fresh round of
    deletions must trigger at the same dead/live point — the prefix-based
    denominator would have needed fewer deletions the second time (m kept
    the old tombstone slots)."""
    srv, u, v = _distinct_pair_server()
    srv.delete(u[:20], v[:20])
    srv.query(np.zeros(4, np.int32), np.zeros(4, np.int32))   # rebuild: live=80
    assert srv.stats.rebuilds == 1
    srv.delete(u[20:35], v[20:35])        # 15/65 = 0.231 < 0.25
    assert not srv._rebuild_due
    srv.delete(u[35:37], v[35:37])        # 17/63 = 0.27 >= 0.25
    assert srv._rebuild_due
    srv.flush()
    assert srv.stats.rebuilds == 2 and not srv.dirty
    es = srv.engine_stats()
    assert es["rebuilds"] == 2 and es["last_rebuild"] is not None
    assert es["rebuild_mode"] == "auto"
    assert es["delta_rebuilds"] == srv.stats.delta_rebuilds

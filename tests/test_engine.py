"""QueryEngine behaviour tests: dispatch-shape budget, batch-size bucketing,
pipelined submits, donated insert parity, serving stats."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DBLIndex, make_graph
from repro.graphs.generators import power_law
from repro.serve.engine import QueryEngine, engine_for, select_backend
from repro.serve.reach_server import ReachabilityServer
from tests.conftest import reach_oracle


def _power_law_index(n=256, m=1200, *, k=8, kp=8, m_extra=64, max_iters=64):
    src, dst = power_law(n, m, seed=5)
    g = make_graph(src, dst, n, m_cap=m + m_extra)
    idx = DBLIndex.build(g, n_cap=n, k=k, k_prime=kp, max_iters=max_iters)
    return idx, src, dst


# -------------------------------------------------- acceptance: ≤2 shapes
def test_10k_batch_two_dispatch_shapes():
    """A 10k-query batch must execute with at most two compiled dispatch
    shapes: one fused label-phase executable and one BFS-chunk executable —
    no per-chunk host-loop recompilation.  Verified by counting jit cache
    entries on a fresh engine."""
    idx, src, dst = _power_law_index()
    rng = np.random.default_rng(0)
    u = rng.integers(0, 256, 10_000).astype(np.int32)
    v = rng.integers(0, 256, 10_000).astype(np.int32)

    eng = QueryEngine(idx, bfs_chunk=256, max_iters=64)
    ans, info = eng.run(idx, u, v, return_stats=True)
    assert info["n_bfs"] > 0, "workload must exercise the BFS path"
    assert eng.stats.bfs_dispatches >= 1
    assert eng.dispatch_shapes() <= 2, (
        f"expected ≤2 compiled dispatch shapes, got {eng.dispatch_shapes()}")

    # exactness against the host-side reference driver and the oracle
    host = idx.query(u, v, bfs_chunk=256, max_iters=64, driver="host")
    np.testing.assert_array_equal(ans, np.asarray(host))
    R = reach_oracle(256, src, dst)
    np.testing.assert_array_equal(ans, R[u, v])


def test_varying_batch_sizes_bucketed_shapes():
    """A serving stream with many distinct batch sizes maps onto a handful
    of padded buckets (the seed host driver compiled one shape per size)."""
    idx, src, dst = _power_law_index()
    rng = np.random.default_rng(1)
    eng = QueryEngine(idx, bfs_chunk=256, max_iters=64, q_block=512)
    R = reach_oracle(256, src, dst)
    for q in (3, 64, 500, 512, 513, 900, 1024, 1500):
        u = rng.integers(0, 256, q).astype(np.int32)
        v = rng.integers(0, 256, q).astype(np.int32)
        ans = eng.run(idx, u, v)
        np.testing.assert_array_equal(ans, R[u, v])
    # 8 distinct batch sizes -> only 3 padded label buckets (512/1024/1536);
    # the seed host driver compiled a fresh verdict shape for every size.
    # BFS adds one executable per (chunk bucket, padded size) actually hit.
    counts = eng.dispatch_shape_counts()
    assert counts["label"] <= 3
    assert eng.dispatch_shapes() <= 10


def test_submit_resolve_pipelining():
    """submit() defers BFS; resolving out of order matches run()."""
    idx, src, dst = _power_law_index()
    rng = np.random.default_rng(2)
    eng = QueryEngine(idx, bfs_chunk=128, max_iters=64)
    batches = [(rng.integers(0, 256, 700).astype(np.int32),
                rng.integers(0, 256, 700).astype(np.int32))
               for _ in range(4)]
    pending = [eng.submit(idx, u, v) for u, v in batches]
    R = reach_oracle(256, src, dst)
    for pend, (u, v) in reversed(list(zip(pending, batches))):
        np.testing.assert_array_equal(pend.resolve(), R[u, v])


def test_flush_coalesces_residues_and_matches_oracle():
    """flush() pools the BFS residues of several micro-batches into one
    right-sized dispatch sequence; answers must equal per-batch run()."""
    idx, src, dst = _power_law_index()
    rng = np.random.default_rng(6)
    eng = QueryEngine(idx, bfs_chunk=256, max_iters=64)
    R = reach_oracle(256, src, dst)
    batches = [(rng.integers(0, 256, q).astype(np.int32),
                rng.integers(0, 256, q).astype(np.int32))
               for q in (900, 300, 1500, 40, 700)]
    pending = [eng.submit(idx, u, v) for u, v in batches]
    pending[1].resolve()              # pre-resolved entries are passed through
    before = eng.stats.bfs_dispatches
    outs = eng.flush(pending)
    for (u, v), out in zip(batches, outs):
        np.testing.assert_array_equal(out, R[u, v])
    total_nu = sum(min(int(p.n_unknown), p.q) for p in pending)
    assert total_nu > 0, "stream must exercise the BFS residue"
    # the 4 unresolved batches shared ceil(total/chunk) dispatches, not 4+
    assert eng.stats.bfs_dispatches - before <= -(-total_nu // 16)


def test_engine_insert_matches_index_insert():
    idx, src, dst = _power_law_index()
    rng = np.random.default_rng(3)
    ns = rng.integers(0, 256, 16).astype(np.int32)
    nd = rng.integers(0, 256, 16).astype(np.int32)
    ref = idx.insert_edges(ns, nd, max_iters=64)
    eng = QueryEngine(idx, bfs_chunk=128, max_iters=64)
    got = eng.insert(ns, nd)
    for name in ("dl_in", "dl_out", "bl_in", "bl_out"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(ref, name)))
    R = reach_oracle(256, np.concatenate([src, ns]),
                     np.concatenate([dst, nd]))
    u = rng.integers(0, 256, 2000).astype(np.int32)
    v = rng.integers(0, 256, 2000).astype(np.int32)
    np.testing.assert_array_equal(eng.query(u, v), R[u, v])


def test_insert_defers_pendings_and_resolves_as_of_submit():
    """insert() must NOT force outstanding submits to resolve: they stay in
    flight across the epoch bump and later resolve against the NEWEST
    snapshot with a per-lane edge-count cutoff, bitwise equal to their
    submit-epoch oracle.  (The old snapshot's buffers are never touched
    again, so a donated insert is free to consume them.)"""
    idx, src, dst = _power_law_index(n=128, m=500, m_extra=64, max_iters=64)
    eng = QueryEngine(idx, bfs_chunk=64, max_iters=64, donate=True)
    rng = np.random.default_rng(8)
    u = rng.integers(0, 128, 600).astype(np.int32)
    v = rng.integers(0, 128, 600).astype(np.int32)
    pend = eng.submit(eng.index, u, v)
    assert pend.epoch == 0 and pend.m_at_submit == 500
    ns = rng.integers(0, 128, 8).astype(np.int32)
    nd = rng.integers(0, 128, 8).astype(np.int32)
    eng.insert(ns, nd)
    # the insert did NOT serialize the pipeline...
    assert pend._result is None and eng.epoch == 1
    # ...and resolution is still exact for the submission-time snapshot
    R_old = reach_oracle(128, src, dst)
    np.testing.assert_array_equal(pend.resolve(), R_old[u, v])
    # post-insert queries see the new graph
    R_new = reach_oracle(128, np.concatenate([src, ns]),
                         np.concatenate([dst, nd]))
    np.testing.assert_array_equal(eng.query(u, v), R_new[u, v])
    # latest consistency on the same deferred stream answers every
    # still-unknown lane at the flush epoch instead
    pend2 = eng.submit(eng.index, u, v)
    ns2 = rng.integers(0, 128, 8).astype(np.int32)
    nd2 = rng.integers(0, 128, 8).astype(np.int32)
    eng.insert(ns2, nd2)
    out2 = eng.flush([pend2], consistency="latest")[0]
    R_new2 = reach_oracle(128, np.concatenate([src, ns, ns2]),
                          np.concatenate([dst, nd, nd2]))
    assert (out2 >= R_new[u, v]).all() and (out2 <= R_new2[u, v]).all()


def test_mixed_epoch_10k_stream_dispatch_shapes():
    """Dispatch-shape regression for epoch coalescing: a 10k-query stream
    whose batches span FOUR snapshot epochs and resolve in cross-epoch
    flushes must still compile <=2 BFS dispatch shapes (one coalesced
    chunk executable; coalescing must not reintroduce shape churn), and
    answers must stay bitwise exact per submit epoch."""
    idx, src, dst = _power_law_index(m_extra=256)
    rng = np.random.default_rng(11)
    eng = QueryEngine(idx, bfs_chunk=256, max_iters=64)
    cur_s, cur_d = list(src), list(dst)
    pendings, snapshots = [], []
    for _ in range(3):
        for q in (2000, 1500):
            u = rng.integers(0, 256, q).astype(np.int32)
            v = rng.integers(0, 256, q).astype(np.int32)
            pendings.append((eng.submit(eng.index, u, v), u, v))
            snapshots.append((list(cur_s), list(cur_d)))
        ns = rng.integers(0, 256, 32).astype(np.int32)
        nd = rng.integers(0, 256, 32).astype(np.int32)
        eng.insert(ns, nd)
        cur_s += ns.tolist()
        cur_d += nd.tolist()
    u = rng.integers(0, 256, 2500).astype(np.int32)
    v = rng.integers(0, 256, 2500).astype(np.int32)
    pendings.append((eng.submit(eng.index, u, v), u, v))
    snapshots.append((list(cur_s), list(cur_d)))
    assert sum(p.q for p, _, _ in pendings) >= 10_000
    outs = eng.flush([p for p, _, _ in pendings])
    assert eng.stats.stale_lanes > 0, \
        "stream must exercise cross-epoch residue lanes"
    counts = eng.dispatch_shape_counts()
    assert counts["bfs"] <= 2, (
        f"mixed-epoch coalescing reintroduced BFS shape churn: {counts}")
    assert counts["label"] <= 3
    for (pend, u, v), (s, d), out in zip(pendings, snapshots, outs):
        R = reach_oracle(256, np.asarray(s), np.asarray(d))
        np.testing.assert_array_equal(out, R[u, v])


def test_server_engine_config_conflicts_rejected():
    idx, _, _ = _power_law_index(n=32, m=80, m_extra=8, max_iters=40)
    idx2, _, _ = _power_law_index(n=64, m=160, m_extra=8, max_iters=40)
    eng = QueryEngine(idx, bfs_chunk=32, max_iters=40)
    with pytest.raises(ValueError):
        ReachabilityServer(idx2, engine=eng)   # two different bound indexes
    with pytest.raises(ValueError):
        ReachabilityServer(None)               # no index at all
    srv = ReachabilityServer(None, engine=eng)  # engine's index is used
    assert srv.index is idx


def test_engine_empty_and_errors():
    idx, _, _ = _power_law_index(n=32, m=80, m_extra=8, max_iters=40)
    eng = QueryEngine(None, bfs_chunk=32, max_iters=40)
    assert eng.run(idx, np.zeros(0, np.int32), np.zeros(0, np.int32)).size == 0
    with pytest.raises(ValueError):
        eng.query([0], [1])           # no bound index
    with pytest.raises(ValueError):
        QueryEngine(backend="cuda")   # unknown backend
    with pytest.raises(ValueError):
        idx.query([0], [1], driver="nope")
    with pytest.raises(ValueError):
        QueryEngine(consistency="eventual")   # unknown consistency mode
    with pytest.raises(ValueError):
        eng.flush([], consistency="nope")
    assert select_backend("jnp") == "jnp"
    assert select_backend("auto") in ("jnp", "pallas")
    # "latest-snapshot" is accepted as an alias for "latest"
    assert QueryEngine(consistency="latest-snapshot").consistency == "latest"


def test_kernel_backends_refuse_the_wrong_host():
    """'pallas' compiles for a TPU and 'pallas-interpret' is the CPU test
    mode: asking for either on the other kind of host raises instead of
    silently running something else."""
    import jax
    compiled, interp = ("pallas", "pallas-interpret")
    if jax.default_backend() == "tpu":
        compiled, interp = interp, compiled
    with pytest.raises(ValueError, match="TPU"):
        select_backend(compiled)
    with pytest.raises(ValueError, match="TPU"):
        QueryEngine(backend=compiled)
    assert QueryEngine(backend=interp).backend == interp
    # the BFS admit kernel is a Pallas kernel too: no interpreter behind
    # a jnp backend's back
    with pytest.raises(ValueError, match="bfs_kernel"):
        QueryEngine(backend="jnp", bfs_kernel=True)


def test_engine_for_is_memoized():
    a = engine_for(bfs_chunk=64, max_iters=33)
    b = engine_for(bfs_chunk=64, max_iters=33)
    c = engine_for(bfs_chunk=128, max_iters=33)
    assert a is b and a is not c


def test_server_round_trip_and_stats():
    idx, src, dst = _power_law_index(n=128, m=500, m_extra=32, max_iters=64)
    srv = ReachabilityServer(idx, bfs_chunk=128, max_iters=64)
    rng = np.random.default_rng(4)
    u = rng.integers(0, 128, 3000).astype(np.int32)
    v = rng.integers(0, 128, 3000).astype(np.int32)
    ans = srv.query(u, v)
    R = reach_oracle(128, src, dst)
    np.testing.assert_array_equal(ans, R[u, v])
    srv.insert([0, 1], [2, 3])
    s = srv.stats.as_dict()
    es = srv.engine_stats()
    assert s["queries"] == 3000 and s["inserts"] == 2
    assert 0.0 <= s["rho"] <= 1.0
    assert es["dispatch_shapes"] <= 2
    assert es["backend"] in ("jnp", "pallas")


def test_rebind_resolves_inflight_pendings_first():
    """Re-binding the engine to a new index must resolve in-flight submits
    from the outgoing lineage against THAT lineage (their cutoffs still
    apply) before letting go of it — under donation the old lineage's
    buffers are unreachable afterwards."""
    idx, src, dst = _power_law_index(n=128, m=500, m_extra=64, max_iters=64)
    idx2, src2, dst2 = _power_law_index(n=128, m=400, m_extra=8, max_iters=64)
    eng = QueryEngine(idx, bfs_chunk=64, max_iters=64, donate=True)
    rng = np.random.default_rng(13)
    u = rng.integers(0, 128, 600).astype(np.int32)
    v = rng.integers(0, 128, 600).astype(np.int32)
    pend = eng.submit(eng.index, u, v)
    ns = rng.integers(0, 128, 8).astype(np.int32)
    nd = rng.integers(0, 128, 8).astype(np.int32)
    eng.insert(ns, nd)                    # epoch bump, pend stays in flight
    assert pend._result is None
    eng.index = idx2                      # re-bind -> pend resolved now
    assert pend._result is not None
    R_old = reach_oracle(128, src, dst)
    np.testing.assert_array_equal(pend.resolve(), R_old[u, v])
    np.testing.assert_array_equal(
        eng.query(u, v), reach_oracle(128, src2, dst2)[u, v])


def test_foreign_engine_flush_uses_pendings_own_index():
    """A pending flushed through a DIFFERENT engine must never be grouped
    into that engine's lineage (per-engine lineage counters collide) — it
    resolves against its own submit-time index."""
    idx1, src1, dst1 = _power_law_index(n=128, m=500, m_extra=8, max_iters=64)
    idx2, _, _ = _power_law_index(n=128, m=400, m_extra=8, max_iters=64)
    eng1 = QueryEngine(idx1, bfs_chunk=64, max_iters=64)
    eng2 = QueryEngine(idx2, bfs_chunk=64, max_iters=64)
    rng = np.random.default_rng(14)
    u = rng.integers(0, 128, 600).astype(np.int32)
    v = rng.integers(0, 128, 600).astype(np.int32)
    pend = eng1.submit(eng1.index, u, v)
    out = eng2.flush([pend])[0]           # wrong engine on purpose
    R1 = reach_oracle(128, src1, dst1)
    np.testing.assert_array_equal(out, R1[u, v])


def test_server_flush_keeps_queue_on_bad_consistency():
    idx, src, dst = _power_law_index(n=64, m=160, m_extra=8, max_iters=40)
    srv = ReachabilityServer(idx, bfs_chunk=32, max_iters=40)
    rng = np.random.default_rng(15)
    u = rng.integers(0, 64, 100).astype(np.int32)
    v = rng.integers(0, 64, 100).astype(np.int32)
    srv.submit(u, v)
    with pytest.raises(ValueError):
        srv.flush(consistency="not-a-mode")
    outs = srv.flush()                    # queue survived the bad call
    assert len(outs) == 1
    np.testing.assert_array_equal(outs[0], reach_oracle(64, src, dst)[u, v])


def test_server_pipelined_submit_flush_across_inserts():
    """ReachabilityServer's pipelined surface: submits accumulate across
    insert() epoch bumps and one flush() resolves them as-of-submit."""
    idx, src, dst = _power_law_index(n=128, m=500, m_extra=64, max_iters=64)
    srv = ReachabilityServer(idx, bfs_chunk=64, max_iters=64)
    rng = np.random.default_rng(9)
    batches, snapshots = [], []
    cur_s, cur_d = list(src), list(dst)
    for _ in range(3):
        u = rng.integers(0, 128, 700).astype(np.int32)
        v = rng.integers(0, 128, 700).astype(np.int32)
        srv.submit(u, v)
        batches.append((u, v))
        snapshots.append((list(cur_s), list(cur_d)))
        ns = rng.integers(0, 128, 8).astype(np.int32)
        nd = rng.integers(0, 128, 8).astype(np.int32)
        srv.insert(ns, nd)
        cur_s += ns.tolist()
        cur_d += nd.tolist()
    assert srv.epoch == 3
    outs = srv.flush()
    for (u, v), (s, d), out in zip(batches, snapshots, outs):
        R = reach_oracle(128, np.asarray(s), np.asarray(d))
        np.testing.assert_array_equal(out, R[u, v])
    s = srv.stats.as_dict()
    assert s["queries"] == 2100 and s["flushes"] == 1
    es = srv.engine_stats()
    assert es["epoch"] == 3 and es["consistency"] == "as-of-submit"


def test_warmup_precompiles():
    idx, _, _ = _power_law_index(n=64, m=160, m_extra=8, max_iters=40)
    eng = QueryEngine(idx, bfs_chunk=64, max_iters=40)
    eng.warmup(idx, batch_sizes=(1, 600), bfs_buckets=(16, 32, 64))
    shapes = eng.dispatch_shapes()
    assert shapes >= 2
    rng = np.random.default_rng(5)
    eng.run(idx, rng.integers(0, 64, 600).astype(np.int32),
            rng.integers(0, 64, 600).astype(np.int32))
    assert eng.dispatch_shapes() == shapes  # nothing new compiled


# ------------------------------------------------- adaptive flush policy
def test_flush_policy_deadline_timing():
    """Deadline policy: nothing flushes before the deadline; once the
    oldest unresolved submit is older than flush_deadline_ms, the next
    submit (or an explicit poll) resolves the pipeline.  Driven by a fake
    clock so the timing is deterministic."""
    idx, src, dst = _power_law_index()
    rng = np.random.default_rng(7)
    u = rng.integers(0, 256, 96).astype(np.int32)
    v = rng.integers(0, 256, 96).astype(np.int32)
    eng = QueryEngine(idx, bfs_chunk=64, max_iters=64,
                      flush_policy="deadline", flush_deadline_ms=10.0)
    t = [0.0]
    eng._clock = lambda: t[0]
    p1 = eng.submit(idx, u, v)
    assert p1._result is None and eng.stats.policy_flushes == 0
    t[0] = 0.005                    # 5ms: before the deadline
    assert not eng.maybe_flush()
    assert p1._result is None
    t[0] = 0.011                    # 11ms: over the deadline
    p2 = eng.submit(idx, v, u)      # the submit itself triggers the flush
    assert p1._result is not None
    assert eng.stats.policy_flushes == 1
    # the fresh batch was pooled into the same policy flush
    assert p2._result is not None
    R = reach_oracle(256, src, dst)
    np.testing.assert_array_equal(p1.resolve(), R[u, v])
    np.testing.assert_array_equal(p2.resolve(), R[v, u])
    # poll path: deadline fires with no new traffic
    p3 = eng.submit(idx, u, v)
    t[0] = 0.030
    assert eng.maybe_flush()
    assert p3._result is not None and eng.stats.policy_flushes == 2


def test_flush_policy_watermark_residue():
    """Watermark policy: the pipeline resolves as soon as the pooled BFS
    residue reaches the watermark — unknown-light batches keep deferring,
    unknown-heavy streams flush early."""
    idx, src, dst = _power_law_index()
    rng = np.random.default_rng(8)
    eng = QueryEngine(idx, bfs_chunk=64, max_iters=64,
                      flush_policy="watermark", flush_watermark=24)
    pendings = []
    while eng.stats.policy_flushes == 0 and len(pendings) < 50:
        u = rng.integers(0, 256, 64).astype(np.int32)
        v = rng.integers(0, 256, 64).astype(np.int32)
        pendings.append((eng.submit(idx, u, v), u, v))
    assert eng.stats.policy_flushes == 1, \
        "watermark never tripped on an unknown-bearing stream"
    resolved = [p for p, _, _ in pendings if p._result is not None]
    assert resolved, "policy flush resolved nothing"
    R = reach_oracle(256, src, dst)
    for p, u, v in pendings:
        np.testing.assert_array_equal(p.resolve(), R[u, v])


def test_flush_policy_validation_and_server_wiring():
    idx, _, _ = _power_law_index(n=64, m=160, m_extra=8, max_iters=40)
    with pytest.raises(ValueError):
        QueryEngine(idx, flush_policy="sometimes")
    with pytest.raises(ValueError):
        QueryEngine(idx, flush_policy="deadline", flush_deadline_ms=0)
    srv = ReachabilityServer(idx, bfs_chunk=64, max_iters=40,
                             flush_policy="deadline", flush_deadline_ms=1e-6)
    rng = np.random.default_rng(9)
    u = rng.integers(0, 64, 32).astype(np.int32)
    srv.submit(u, u)
    srv.poll()
    # with a ~1ns deadline the submit (or the poll) must have auto-flushed
    assert srv.engine.stats.policy_flushes == 1
    assert srv.engine_stats()["flush_policy"] == "deadline"
    outs = srv.flush()              # answers still returned in order
    assert len(outs) == 1 and (outs[0] == np.ones(32, bool)).all()


# --------------------------------------------------------- AOT serving
def test_aot_cache_round_trip(tmp_path):
    """Cold-start AOT: first engine exports its verdict + BFS-bucket
    executables to the disk cache; a second (fresh) engine loads them as
    deserialized jax.export artifacts — cache hits, identical answers,
    and the dispatch-shape accounting still holds."""
    idx, src, dst = _power_law_index()
    rng = np.random.default_rng(11)
    u = rng.integers(0, 256, 700).astype(np.int32)
    v = rng.integers(0, 256, 700).astype(np.int32)

    e1 = QueryEngine(idx, bfs_chunk=64, max_iters=64)
    e1.aot_warmup(idx, tmp_path)
    assert e1.aot_cache.stores > 0 and e1.aot_cache.hits == 0
    files = list(tmp_path.glob("*.jaxexp"))
    assert len(files) == e1.aot_cache.stores
    base = e1.run(idx, u, v)

    e2 = QueryEngine(idx, bfs_chunk=64, max_iters=64)
    e2.aot_warmup(idx, tmp_path)
    assert e2.aot_cache.hits == e1.aot_cache.stores \
        and e2.aot_cache.misses == 0
    got = e2.run(idx, u, v)
    np.testing.assert_array_equal(base, got)
    R = reach_oracle(256, src, dst)
    np.testing.assert_array_equal(got, R[u, v])
    assert e2.dispatch_shapes() >= 1   # ShapeDispatcher accounting works

    # key stability: a third warmup re-hits the same files (no new stores)
    e3 = QueryEngine(idx, bfs_chunk=64, max_iters=64)
    e3.aot_warmup(idx, tmp_path)
    assert e3.aot_cache.stores == 0
    assert len(list(tmp_path.glob("*.jaxexp"))) == len(files)


def test_aot_cache_corrupt_entry_degrades_to_miss(tmp_path):
    idx, _, _ = _power_law_index(n=64, m=160, m_extra=8, max_iters=40)
    e1 = QueryEngine(idx, bfs_chunk=32, max_iters=40)
    e1.aot_warmup(idx, tmp_path)
    for f in tmp_path.glob("*.jaxexp"):
        f.write_bytes(b"garbage")
    from repro.serve.aot import AOTCacheWarning
    e2 = QueryEngine(idx, bfs_chunk=32, max_iters=40)
    with pytest.warns(AOTCacheWarning):
        e2.aot_warmup(idx, tmp_path)
    assert e2.aot_cache.hits == 0     # every entry degraded to a miss
    rng = np.random.default_rng(3)
    u = rng.integers(0, 64, 128).astype(np.int32)
    ans = e2.run(idx, u, u)           # serving still works (live jit)
    assert ans.all()


def test_aot_rejects_meshed_layouts(tmp_path):
    idx, _, _ = _power_law_index(n=64, m=160, m_extra=8, max_iters=40)
    from repro.core.distributed import vertex_mesh
    eng = QueryEngine(idx, bfs_chunk=32, max_iters=40,
                      vertex_mesh=vertex_mesh(1))
    with pytest.raises(ValueError):
        eng.aot_warmup(eng.index, tmp_path)


def test_aot_entry_under_an_older_output_schema_misses(tmp_path,
                                                      monkeypatch):
    """Entries exported before the coalesced program returned its level
    counts were tagged ``label`` and ``coalesced-<chunk>``; a cache holding
    them must miss (loading one would serve a one-output program), and the
    engine then stores entries under its own tags."""
    from repro.serve import engine as E
    from repro.serve.aot import AOTCache
    idx, _, _ = _power_law_index(n=64, m=160, m_extra=8, max_iters=40)
    real_key = AOTCache.key

    def old_key(tag, *a, **kw):
        return real_key(tag.removesuffix(f"-v{E.OUTPUT_SCHEMA}"), *a, **kw)

    with monkeypatch.context() as m:
        m.setattr(AOTCache, "key", staticmethod(old_key))
        old = QueryEngine(idx, bfs_chunk=32, max_iters=40)
        old.aot_warmup(idx, tmp_path)
    assert old.aot_cache.stores > 0
    new = QueryEngine(idx, bfs_chunk=32, max_iters=40)
    new.aot_warmup(idx, tmp_path)
    assert new.aot_cache.hits == 0
    assert new.aot_cache.stores == old.aot_cache.stores
    again = QueryEngine(idx, bfs_chunk=32, max_iters=40)
    again.aot_warmup(idx, tmp_path)
    assert again.aot_cache.hits == old.aot_cache.stores
    u = np.arange(64, dtype=np.int32)
    np.testing.assert_array_equal(again.run(idx, u, u[::-1]),
                                  new.run(idx, u, u[::-1]))


# ------------------------------- AOT cache-key completeness (PR 7)
def test_aot_cache_key_includes_every_baked_knob(tmp_path):
    """Flipping any executable-baked engine knob must MISS the cache — a
    hit under different knobs would silently serve the old semantics
    (e.g. a stale frontier_dtype changing the BFS lane layout).  This
    regression-pins the config blob: frontier_dtype / out_dtype /
    plane_repr / bfs_kernel / max_iters / halo_mode / hub_count /
    halo_caps all key the entries."""
    idx, _, _ = _power_law_index(n=64, m=160, m_extra=8, max_iters=40)
    base_kw = dict(bfs_chunk=32, max_iters=40)
    e1 = QueryEngine(idx, **base_kw)
    e1.aot_warmup(idx, tmp_path)
    assert e1.aot_cache.stores > 0
    # bfs_kernel needs a kernel backend: flip it against a kernel-backed base
    kernel_kw = dict(base_kw, backend="pallas-interpret")
    QueryEngine(idx, **kernel_kw).aot_warmup(idx, tmp_path)
    for base, flip in ((base_kw, dict(frontier_dtype="int32")),
                       (base_kw, dict(out_dtype="int32")),
                       (base_kw, dict(plane_repr="packed")),
                       (kernel_kw, dict(bfs_kernel=True)),
                       (base_kw, dict(max_iters=48)),
                       (base_kw, dict(halo_mode="sparse")),
                       (base_kw, dict(hub_count=8)),
                       (base_kw, dict(halo_caps=(8, 32)))):
        e2 = QueryEngine(idx, **{**base, **flip})
        e2.aot_warmup(idx, tmp_path)
        assert e2.aot_cache.hits == 0, f"stale AOT hit under {flip}"
        assert e2.aot_cache.stores > 0, flip
    # sanity: unchanged knobs still hit
    e3 = QueryEngine(idx, **base_kw)
    e3.aot_warmup(idx, tmp_path)
    assert e3.aot_cache.stores == 0 and e3.aot_cache.hits > 0


# ------------------------------------ empty-index serving paths (PR 7)
def _empty_index(n=32, m_cap=64):
    g = make_graph(np.zeros(0, np.int32), np.zeros(0, np.int32), n,
                   m_cap=m_cap)
    return DBLIndex.build(g, n_cap=n, k=4, k_prime=4, max_iters=16)


def test_engine_empty_index_submit_flush_poll():
    """An engine bound to an index with zero edges must serve the whole
    submit/flush/poll surface without dispatching a BFS or dividing by
    zero: only self-queries are reachable."""
    idx = _empty_index()
    eng = QueryEngine(idx, bfs_chunk=16, max_iters=16)
    assert eng._m_now == 0
    u = np.array([0, 3, 7, 7], np.int32)
    v = np.array([0, 4, 7, 2], np.int32)
    pend = eng.submit(idx, u, v)
    assert not eng.maybe_flush()          # no policy => no-op, no dispatch
    (ans,) = eng.flush([pend])
    np.testing.assert_array_equal(ans, u == v)
    assert eng.stats.bfs_dispatches == 0  # labels answer everything
    # run() on an empty batch against the empty index
    out, st_ = eng.run(idx, np.zeros(0, np.int32), np.zeros(0, np.int32),
                       return_stats=True)
    assert out.shape == (0,) and st_["rho"] == 1.0


def test_engine_empty_index_policies_and_mutation():
    """Deadline/watermark policies on an engine with an empty pipeline and
    an empty index: flush_due()/maybe_flush() are no-ops (no division by
    zero on the empty residue), and the first insert starts serving."""
    for policy, kw in (("deadline", dict(flush_deadline_ms=5.0)),
                       ("watermark", dict(flush_watermark=4))):
        idx = _empty_index()
        eng = QueryEngine(idx, bfs_chunk=16, max_iters=16,
                          flush_policy=policy, **kw)
        t = [0.0]
        eng._clock = lambda: t[0]
        assert not eng.flush_due()        # empty pipeline: nothing due
        assert not eng.maybe_flush()
        t[0] = 1.0                        # way past any deadline
        assert not eng.flush_due()        # still nothing in flight
        u = np.array([1, 2], np.int32)
        pend = eng.submit(idx, u, u + 1)  # unreachable: rides the pipeline
        t[0] = 2.0
        eng.maybe_flush()                 # deadline fires on a poll; the
        pend.resolve()                    # watermark one resolves lazily
        np.testing.assert_array_equal(pend.resolve(), [False, False])
        # first insert on the empty index, then a reachable query
        eng.insert(np.array([1], np.int32), np.array([2], np.int32))
        np.testing.assert_array_equal(
            eng.query(np.array([1], np.int32), np.array([2], np.int32)),
            [True])
        # delete back to empty-live and rebuild: still serving
        eng.delete(np.array([1], np.int32), np.array([2], np.int32))
        eng.rebuild(mode="full", max_iters=16)
        np.testing.assert_array_equal(
            eng.query(np.array([1], np.int32), np.array([2], np.int32)),
            [False])


def test_engine_empty_index_packed_parity():
    """The packed plane_repr serves the empty index too (the fixpoint's
    zero-live-edge round must not fabricate bits)."""
    idx_b = _empty_index()
    g = idx_b.graph
    idx_p = DBLIndex.build(g, n_cap=32, k=4, k_prime=4, max_iters=16,
                           plane_repr="packed")
    for f in ("dl_in", "dl_out", "bl_in", "bl_out"):
        np.testing.assert_array_equal(np.asarray(getattr(idx_b, f)),
                                      np.asarray(getattr(idx_p, f)))
    eng = QueryEngine(idx_p, bfs_chunk=16, max_iters=16,
                      plane_repr="packed", frontier_dtype="packed")
    u = np.array([0, 5, 9], np.int32)
    np.testing.assert_array_equal(eng.query(u, u), [True] * 3)
    np.testing.assert_array_equal(eng.query(u, u + 1), [False] * 3)


# ------------------------------------------------- streamed-kernel serving
def test_engine_streaming_serving_parity():
    """streaming=True routes the PR-7 double-buffered kernels through the
    serving path (verdicts + BFS admit planes): answers must match the jnp
    engine bitwise across a mixed query/insert/delete/rebuild stream."""
    idx, src, dst = _power_law_index()
    eng_j = QueryEngine(idx, bfs_chunk=64, max_iters=64, backend="jnp")
    eng_s = QueryEngine(idx, bfs_chunk=64, max_iters=64,
                        backend="pallas-interpret", bfs_kernel=True,
                        streaming=True)
    assert eng_s.streaming
    rng = np.random.default_rng(31)
    for r in range(3):
        u = rng.integers(0, 256, 200).astype(np.int32)
        v = rng.integers(0, 256, 200).astype(np.int32)
        np.testing.assert_array_equal(eng_j.query(u, v), eng_s.query(u, v))
        ns = rng.integers(0, 256, 16).astype(np.int32)
        nd = rng.integers(0, 256, 16).astype(np.int32)
        eng_j.insert(ns, nd)
        eng_s.insert(ns, nd)
    eng_j.delete(src[:25], dst[:25])
    eng_s.delete(src[:25], dst[:25])
    u = rng.integers(0, 256, 300).astype(np.int32)
    v = rng.integers(0, 256, 300).astype(np.int32)
    np.testing.assert_array_equal(eng_j.query(u, v), eng_s.query(u, v))
    eng_j.rebuild(mode="full", max_iters=64)
    eng_s.rebuild(mode="full", max_iters=64)
    np.testing.assert_array_equal(eng_j.query(u, v), eng_s.query(u, v))


def test_engine_streaming_knob_validation():
    """streaming requires a kernel backend, and the vertex-sharded layout
    (which never dispatches the query kernels) refuses it outright."""
    idx, _, _ = _power_law_index(m=600)
    with pytest.raises(ValueError, match="streaming"):
        QueryEngine(idx, backend="jnp", streaming=True)
    from repro.core import distributed as D
    with pytest.raises(ValueError, match="vertex-sharded"):
        QueryEngine(backend="pallas-interpret", streaming=True,
                    vertex_mesh=D.vertex_mesh(1))


def test_engine_streaming_il_falls_back_with_one_warning():
    """An il-enabled index on a streaming engine must SERVE (grid-kernel
    fallback), not crash in the kernel layer — warning exactly once PER
    ENGINE (a fresh engine signals again; no process-wide latch), with
    answers bitwise equal to the non-streaming engine."""
    import warnings as _w
    from repro.kernels.dbl_query.ops import StreamILFallbackWarning
    src, dst = power_law(128, 700, seed=41)
    g = make_graph(src, dst, 128, m_cap=764)
    idx = DBLIndex.build(g, n_cap=128, k=8, k_prime=8, max_iters=64,
                         families=("dl", "bl", "il"), il_dim=2, il_seed=3)
    rng = np.random.default_rng(43)
    u = rng.integers(0, 128, 150).astype(np.int32)
    v = rng.integers(0, 128, 150).astype(np.int32)
    eng_g = QueryEngine(idx, bfs_chunk=64, max_iters=64,
                        backend="pallas-interpret")
    eng_s = QueryEngine(idx, bfs_chunk=64, max_iters=64,
                        backend="pallas-interpret", streaming=True)
    with pytest.warns(StreamILFallbackWarning, match="grid kernel"):
        a = eng_s.query(u, v)
    with _w.catch_warnings():
        _w.simplefilter("error")     # second dispatch must stay silent
        b = eng_s.query(v, u)
    np.testing.assert_array_equal(a, eng_g.query(u, v))
    np.testing.assert_array_equal(b, eng_g.query(v, u))
    # the latch is per engine instance: a NEW streaming engine must not be
    # silently downgraded by the first one's warning
    eng_s2 = QueryEngine(idx, bfs_chunk=64, max_iters=64,
                         backend="pallas-interpret", streaming=True)
    with pytest.warns(StreamILFallbackWarning, match="grid kernel"):
        a2 = eng_s2.query(u, v)
    np.testing.assert_array_equal(a2, a)

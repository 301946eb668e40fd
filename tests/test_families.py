"""Label-family registry + interval ("il") plug-in family suite.

Contracts pinned here:

- **registry** — ``families`` tuples resolve through ``core.families``;
  the mandatory fused DL/BL core must lead, unknown names and duplicates
  raise, and the default tuple builds an index whose pytree (and bits)
  are EXACTLY the pre-registry index.
- **exactness** — a ``("dl", "bl", "il")`` index answers bitwise
  identical to the dense transitive-closure oracle AND to the DL+BL
  baseline through the full maintained lifecycle (build / insert /
  delete / delta + full rebuild): the interval family is a pure negative
  prune, never a semantics change.
- **soundness classes** — IL negatives are insert-monotone (no per-lane
  edge-count gate) but NOT deletion-sound: while the index is
  tombstone-dirty the family contributes nothing (mirrors the
  test_deletions.py verdict-downgrade contract), and the rebuild's full
  re-draw from the committed seed re-enables it — delta bitwise equal to
  full.
- **telemetry** — ``engine.stats.prune_hits`` attributes every resolved
  lane to exactly one family (dl/bl/il/thm/bfs sums to queries), reports
  zero IL hits while dirty, and surfaces through
  ``ReachabilityServer.engine_stats()``.
- **AOT completeness** — the cache key carries (families, il_dim,
  il_seed): flipping the rank seed alone (identical avals!) must miss.
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DBLIndex, make_graph
from repro.core import families as F
from repro.core import interval as IL
from repro.core import query as Q
from repro.serve.engine import QueryEngine
from repro.serve.reach_server import ReachabilityServer
from tests.conftest import reach_oracle, random_graph

ROOT = pathlib.Path(__file__).resolve().parent.parent
FAM = dict(families=("dl", "bl", "il"), il_dim=4, il_seed=7)


def _all_pairs(n):
    u, v = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    return u.ravel().astype(np.int32), v.ravel().astype(np.int32)


def _graph(seed, *, n_max=24, m_max=80, m_extra=160):
    rng = np.random.default_rng(seed)
    n, src, dst = random_graph(rng, n_max=n_max, m_max=m_max)
    return n, src, dst, make_graph(src, dst, n, m_cap=len(src) + m_extra)


# ------------------------------------------------------------- registry
def test_registry_resolves_and_validates():
    dl, bl, il = F.resolve(("dl", "bl", "il"))
    assert (dl.fused_core, bl.fused_core, il.fused_core) == (
        True, True, False)
    assert il.monoid == "min" and il.verdict == "negative"
    assert il.while_dirty == "none" and not il.packable
    assert il.plane_width(4) == 8
    with pytest.raises(ValueError, match="must start with"):
        F.resolve(("il",))
    with pytest.raises(ValueError, match="must start with"):
        F.resolve(("bl", "dl", "il"))
    with pytest.raises(KeyError, match="unknown label family"):
        F.resolve(("dl", "bl", "nope"))
    with pytest.raises(ValueError, match="duplicate"):
        F.resolve(("dl", "bl", "il", "il"))


def test_default_families_identical_to_pre_registry_index():
    n, src, dst, g = _graph(0)
    base = DBLIndex.build(g, n_cap=n, k=8, k_prime=8)
    via = DBLIndex.build(g, n_cap=n, k=8, k_prime=8,
                         families=F.CORE_FAMILIES)
    assert base.il_in is None and via.il_in is None
    assert base.families == via.families == ("dl", "bl")
    assert base.il is None and base.il_dim is None
    for f in ("dl_in", "dl_out", "bl_in", "bl_out"):
        np.testing.assert_array_equal(np.asarray(getattr(base, f)),
                                      np.asarray(getattr(via, f)))


def test_rank_planes_deterministic_and_bounded():
    a = IL.rank_plane(32, 4, 7)
    b = IL.rank_plane(32, 4, 7)
    c = IL.rank_plane(32, 4, 8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) != np.asarray(c)).any()
    r = np.asarray(a)[:, :4]
    np.testing.assert_array_equal(np.asarray(a)[:, 4:], -r)
    assert (np.abs(r) < 2 ** 30).all()


# ------------------------------------------------------------ exactness
@pytest.mark.parametrize("seed", [1, 2, 5])
def test_il_index_exact_and_equal_to_baseline(seed):
    n, src, dst, g = _graph(seed)
    R = reach_oracle(n, src, dst)
    u, v = _all_pairs(n)
    base = DBLIndex.build(g, n_cap=n, k=8, k_prime=8)
    idx = DBLIndex.build(g, n_cap=n, k=8, k_prime=8, **FAM)
    assert idx.families == ("dl", "bl", "il")
    assert idx.il_dim == 4 and int(np.asarray(idx.il_seed)) == 7
    a = np.asarray(idx.query(u, v, driver="host"))
    np.testing.assert_array_equal(a, R[u, v])
    np.testing.assert_array_equal(
        a, np.asarray(base.query(u, v, driver="host")))
    # IL verdicts only strengthen the label phase: flips are -1 -> 0 only
    vd_b = np.asarray(base.label_verdicts(u, v))
    vd_i = np.asarray(idx.label_verdicts(u, v))
    diff = vd_b != vd_i
    assert ((vd_b[diff] == -1) & (vd_i[diff] == 0)).all()


def test_il_negative_is_sound_prune():
    """Every lane IL prunes is truly unreachable (against the oracle)."""
    n, src, dst, g = _graph(3)
    R = reach_oracle(n, src, dst)
    idx = DBLIndex.build(g, n_cap=n, k=8, k_prime=8, **FAM)
    u, v = _all_pairs(n)
    neg = np.asarray(IL.il_negative(idx.il_out[u], idx.il_out[v],
                                    idx.il_in[u], idx.il_in[v]))
    assert not R[u, v][neg].any()


@pytest.mark.parametrize("seed", [4, 9])
def test_il_lifecycle_insert_delete_rebuild(seed):
    n, src, dst, g = _graph(seed)
    rng = np.random.default_rng(seed)
    base = DBLIndex.build(g, n_cap=n, k=8, k_prime=8)
    idx = DBLIndex.build(g, n_cap=n, k=8, k_prime=8, **FAM)
    cur_s, cur_d = list(src), list(dst)
    for _ in range(2):
        ns = rng.integers(0, n, 12).astype(np.int32)
        nd = rng.integers(0, n, 12).astype(np.int32)
        base = base.insert_edges(ns, nd)
        idx = idx.insert_edges(ns, nd)
        cur_s += ns.tolist()
        cur_d += nd.tolist()
        u, v = _all_pairs(n)
        R = reach_oracle(n, np.asarray(cur_s), np.asarray(cur_d))
        np.testing.assert_array_equal(
            np.asarray(idx.query(u, v, driver="host")), R[u, v])
    # delete -> dirty: IL planes are stale but must not influence answers
    kill = min(8, len(src))
    base = base.delete_edges(src[:kill], dst[:kill])
    idx = idx.delete_edges(src[:kill], dst[:kill])
    assert idx.is_dirty
    u, v = _all_pairs(n)
    dead = set(zip(src[:kill].tolist(), dst[:kill].tolist()))
    live = [(s, d) for s, d in zip(cur_s, cur_d) if (s, d) not in dead]
    ls, ld = (np.asarray([e[0] for e in live], np.int32),
              np.asarray([e[1] for e in live], np.int32))
    R = reach_oracle(n, ls, ld)
    np.testing.assert_array_equal(
        np.asarray(idx.query(u, v, driver="host")), R[u, v])
    np.testing.assert_array_equal(
        np.asarray(idx.query(u, v, driver="host")),
        np.asarray(base.query(u, v, driver="host")))
    # rebuild repairs the family by a full re-draw from the SAME seed:
    # delta bitwise equal to full, and the planes answer again
    full = idx.rebuild(mode="full")
    delta = idx.rebuild(mode="delta")
    for f in ("il_in", "il_out"):
        np.testing.assert_array_equal(np.asarray(getattr(delta, f)),
                                      np.asarray(getattr(full, f)))
    assert int(np.asarray(delta.il_seed)) == FAM["il_seed"]
    np.testing.assert_array_equal(
        np.asarray(delta.query(u, v, driver="host")), R[u, v])


# ---------------------------------------------------- dirty gating (IL)
def test_il_gated_off_exactly_while_dirty():
    """Mirror of the test_deletions.py downgrade contract for IL: the
    label phase must stop consulting interval planes the moment the index
    goes dirty — even planes poisoned to claim everything-unreachable may
    not flip one verdict — and must consult them again after rebuild."""
    n, src, dst, g = _graph(6, m_extra=64)
    idx = DBLIndex.build(g, n_cap=n, k=8, k_prime=8, **FAM)
    u, v = _all_pairs(n)
    # poisoned IL planes: strictly increasing per-row ranks make EVERY
    # ordered pair (u != v) violate containment in one direction or the
    # other — if the dirty path consulted them, every non-self lane
    # would be (unsoundly) pruned
    ramp = jnp.broadcast_to(
        jnp.arange(idx.n_cap, dtype=jnp.int32)[:, None], idx.il_in.shape)
    dirty = idx.delete_edges(src[:1], dst[:1])._replace(
        il_in=ramp, il_out=ramp)
    live_mask = np.ones(len(src), bool)
    live_mask[0] = False
    R = reach_oracle(n, src[live_mask], dst[live_mask])
    np.testing.assert_array_equal(
        np.asarray(dirty.query(u, v, driver="host")), R[u, v])
    # engine path too, with the hit counter agreeing
    eng = QueryEngine(dirty, bfs_chunk=64, donate=False)
    np.testing.assert_array_equal(np.asarray(eng.query(u, v)), R[u, v])
    assert eng.stats.prune_hits["il"] == 0
    # rebuild re-derives from the committed seed -> IL active again
    clean = dirty.rebuild(mode="full")
    np.testing.assert_array_equal(
        np.asarray(clean.query(u, v, driver="host")), R[u, v])
    eng2 = QueryEngine(clean, bfs_chunk=64, donate=False)
    np.testing.assert_array_equal(np.asarray(eng2.query(u, v)), R[u, v])
    neg = np.asarray(IL.il_negative(clean.il_out[u], clean.il_out[v],
                                    clean.il_in[u], clean.il_in[v]))
    if neg.any():   # family re-enabled: its negatives are attributed again
        assert eng2.stats.prune_hits["il"] > 0


# ------------------------------------------------------------ telemetry
def test_prune_hits_partition_queries():
    n, src, dst, g = _graph(8)
    idx = DBLIndex.build(g, n_cap=n, k=8, k_prime=8, **FAM)
    eng = QueryEngine(idx, bfs_chunk=64, donate=False)
    rng = np.random.default_rng(1)
    for q in (7, 64, 129):
        u = rng.integers(0, n, q).astype(np.int32)
        v = rng.integers(0, n, q).astype(np.int32)
        eng.query(u, v)
    hits = eng.stats.prune_hits
    assert set(hits) == {"dl", "bl", "il", "thm", "bfs"}
    assert all(c >= 0 for c in hits.values())
    assert sum(hits.values()) == eng.stats.queries == 7 + 64 + 129
    assert eng.stats.as_dict()["prune_hits"] == hits


def test_prune_hits_surface_through_server():
    n, src, dst, g = _graph(12)
    idx = DBLIndex.build(g, n_cap=n, k=8, k_prime=8, **FAM)
    srv = ReachabilityServer(idx, bfs_chunk=64)
    rng = np.random.default_rng(2)
    u = rng.integers(0, n, 100).astype(np.int32)
    v = rng.integers(0, n, 100).astype(np.int32)
    srv.query(u, v)
    d = srv.engine_stats()
    assert "prune_hits" in d
    assert sum(d["prune_hits"].values()) == 100


# ------------------------------------------------------------ AOT key
def test_aot_key_covers_families_dim_and_seed(tmp_path):
    """Flip-one-knob regression: identical avals with a different rank
    seed (or a families change) MUST miss the AOT cache — a hit would
    silently serve verdicts computed against the wrong rank draw."""
    n, src, dst, g = _graph(10)
    idx = DBLIndex.build(g, n_cap=n, k=8, k_prime=8, **FAM)
    e1 = QueryEngine(idx, bfs_chunk=64, donate=False)
    e1.aot_warmup(idx, tmp_path)
    assert e1.aot_cache.stores > 0

    # same everything -> all hits
    e2 = QueryEngine(idx, bfs_chunk=64, donate=False)
    e2.aot_warmup(idx, tmp_path)
    assert e2.aot_cache.hits == e1.aot_cache.stores
    assert e2.aot_cache.stores == 0

    # same avals, different il_seed -> zero hits
    idx_seed = DBLIndex.build(g, n_cap=n, k=8, k_prime=8,
                              families=FAM["families"],
                              il_dim=FAM["il_dim"], il_seed=99)
    assert [tuple(x.shape) for x in (idx_seed.il_in, idx_seed.il_out)] \
        == [tuple(x.shape) for x in (idx.il_in, idx.il_out)]
    e3 = QueryEngine(idx_seed, bfs_chunk=64, donate=False)
    e3.aot_warmup(idx_seed, tmp_path)
    assert e3.aot_cache.hits == 0 and e3.aot_cache.stores > 0

    # families flip -> zero hits (aval change also protects, key must too)
    idx_core = DBLIndex.build(g, n_cap=n, k=8, k_prime=8)
    e4 = QueryEngine(idx_core, bfs_chunk=64, donate=False)
    e4.aot_warmup(idx_core, tmp_path)
    assert e4.aot_cache.hits == 0

    # il_dim flip -> zero hits
    idx_dim = DBLIndex.build(g, n_cap=n, k=8, k_prime=8,
                             families=FAM["families"], il_dim=2,
                             il_seed=FAM["il_seed"])
    e5 = QueryEngine(idx_dim, bfs_chunk=64, donate=False)
    e5.aot_warmup(idx_dim, tmp_path)
    assert e5.aot_cache.hits == 0


# ------------------------------------------------------- kernel parity
def test_grid_kernel_and_admit_plane_parity_with_il():
    from repro.kernels.dbl_query import ops as QK
    from repro.kernels.bfs_prune import ops as BK
    n, src, dst, g = _graph(14)
    k = min(8, n)
    idx = DBLIndex.build(g, n_cap=n, k=k, k_prime=k, **FAM)
    u, v = _all_pairs(n)
    uj, vj = jnp.asarray(u), jnp.asarray(v)
    ref = np.asarray(Q.label_verdicts(idx.packed, uj, vj, il=idx.il))
    got = np.asarray(QK.query_verdicts(idx.packed, uj, vj, il=idx.il,
                                       q_block=128, interpret=True))
    np.testing.assert_array_equal(ref, got)
    # streaming+il no longer raises: the dispatch falls back to the grid
    # kernel (StreamILFallbackWarning, bitwise-identical verdicts)
    with pytest.warns(QK.StreamILFallbackWarning, match="grid kernel"):
        via_stream = np.asarray(QK.query_verdicts(
            idx.packed, uj, vj, il=idx.il, q_block=128, interpret=True,
            streaming=True))
    np.testing.assert_array_equal(ref, via_stream)
    # admit plane: interval AND wraps the bit-plane kernel output
    q = min(64, len(u))
    for il_on in (None, jnp.ones((q,), jnp.bool_),
                  jnp.zeros((q,), jnp.bool_)):
        want = np.asarray(Q._admit_plane(
            idx.packed, uj[:q], vj[:q], n, il=idx.il, il_on=il_on))
        have = np.asarray(BK.admit_plane(
            idx.packed, uj[:q], vj[:q], il=idx.il, il_on=il_on,
            n_block=128, q_block=32, interpret=True))
        np.testing.assert_array_equal(want, have)


# -------------------------------------------------------------- bench
def test_bench_rejects_unknown_sections():
    from benchmarks.bench_dbl_perf import main
    with pytest.raises(ValueError, match="unknown bench sections"):
        main(sections=["no_such_section"])


# ---------------------------------------------------- sharded (slow)
@pytest.mark.slow
def test_sharded_il_differential():
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{ROOT}"
    out = subprocess.run(
        [sys.executable, str(ROOT / "tests/distributed/run_sharded_il.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "SHARDED_IL_OK" in out.stdout

"""The vertex-sharded BFS residue counts its levels as the replicated one
does.  Runs in a subprocess with 4 forced host devices: the coalesced
programs of both layouts return the same answers, per-lane hit levels and
level count on the same lanes, and a submit/insert/flush stream leaves
both engines with the same ``EngineStats`` BFS counters.

Invoked by tests/test_sharded_planes.py; exits non-zero on mismatch.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import DBLIndex, make_graph  # noqa: E402
from repro.core import distributed as D  # noqa: E402
from repro.core import query as Q  # noqa: E402
from repro.serve.engine import QueryEngine  # noqa: E402

N = 256
COUNTERS = ("bfs_levels", "bfs_slot_levels", "bfs_open_lane_levels",
            "bfs_proof_levels", "bfs_dispatches")


def engines():
    """Short forward edges and a tenth of random ones: a deep residue."""
    rng = np.random.default_rng(21)
    m = 700
    src = rng.integers(0, N - 1, m).astype(np.int32)
    dst = np.minimum(src + rng.integers(1, 9, m), N - 1).astype(np.int32)
    jump = rng.random(m) < 0.1
    dst[jump] = rng.integers(0, N, int(jump.sum()))
    g = make_graph(src, dst, N, m_cap=m + 256)
    idx = DBLIndex.build(g, n_cap=N, k=2, k_prime=2, max_iters=64)
    kw = dict(bfs_chunk=64, max_iters=64)
    return (QueryEngine(idx, **kw),
            QueryEngine(idx, vertex_mesh=D.vertex_mesh(4), **kw), rng)


def coalesced_outputs_match(eng_r, eng_s, rng):
    """One 64-lane dispatch of each layout on the same residue lanes (a
    few dead, some with a stale edge-count cutoff)."""
    idx = eng_r.index
    u = rng.integers(0, N, 8000).astype(np.int32)
    v = rng.integers(0, N, 8000).astype(np.int32)
    res = np.flatnonzero(np.asarray(idx.label_verdicts(u, v)) == -1)[:60]
    assert res.size == 60, res.size
    uu = np.concatenate([u[res], np.full(4, N, np.int32)])
    vv = np.concatenate([v[res], np.zeros(4, np.int32)])
    m = int(np.asarray(idx.graph.m))
    cuts = np.where(rng.random(64) < 0.3, m - 50, Q.FRESH_CUT)
    args = (jnp.asarray(uu), jnp.asarray(vv), jnp.asarray(cuts, jnp.int32))
    clean = jnp.asarray(False)
    out_r = eng_r._coal_phases[64](idx.graph, idx.packed, idx.il, *args,
                                   clean)
    si = eng_s.index
    out_s = eng_s._coal_phases[64](si.graph, si.packed, si.il, *args, clean,
                                   *eng_s._coalesced_extra_args())
    for name, a, b in zip(("answers", "hit_level", "levels"), out_r, out_s):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and (a == b).all(), f"{name} diverged"
    hit_level, levels = np.asarray(out_r[1]), int(out_r[2])
    assert (hit_level[-4:] == -1).all()          # dead lanes did not ride
    assert (hit_level > 0).any() and levels > hit_level.max()
    print("coalesced hit levels and level count OK")


def stream_counters_match(eng_r, eng_s, rng):
    pend_r, pend_s = [], []
    for _ in range(4):
        u = rng.integers(0, N, 300).astype(np.int32)
        v = rng.integers(0, N, 300).astype(np.int32)
        pend_r.append(eng_r.submit(eng_r.index, u, v))
        pend_s.append(eng_s.submit(eng_s.index, u, v))
        ns = rng.integers(0, N, 16).astype(np.int32)
        nd = rng.integers(0, N, 16).astype(np.int32)
        eng_r.insert(ns, nd)
        eng_s.insert(ns, nd)
    for a, b in zip(eng_r.flush(pend_r), eng_s.flush(pend_s)):
        assert (a == b).all(), "answers diverged"
    r, s = eng_r.stats.as_dict(), eng_s.stats.as_dict()
    assert r["bfs_levels"] > 0 and r["stale_lanes"] > 0
    for k in COUNTERS:
        assert r[k] == s[k], (k, r[k], s[k])
    print("stream BFS counters OK")


def main():
    assert len(jax.devices()) == 4, jax.devices()
    eng_r, eng_s, rng = engines()
    coalesced_outputs_match(eng_r, eng_s, rng)
    stream_counters_match(eng_r, eng_s, rng)
    print("BFS_COUNTERS_OK")


if __name__ == "__main__":
    main()

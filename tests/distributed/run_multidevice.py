"""Multi-device DBL checks. Run in a subprocess with 8 host devices:
sharded build/query/insert must equal the single-logical-device results.

Invoked by test_distributed.py; exits non-zero on mismatch.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import DBLIndex, make_graph  # noqa: E402
from repro.core import distributed as D  # noqa: E402
from repro.graphs.generators import power_law  # noqa: E402
from repro.launch.mesh import auto_mesh  # noqa: E402
from repro.serve.engine import QueryEngine  # noqa: E402


def main():
    assert len(jax.devices()) == 8, jax.devices()
    n, m = 512, 4096
    src, dst = power_law(n, m, seed=3)
    m_cap = m + 64
    g = make_graph(src, dst, n, m_cap=m_cap)

    # single-device reference
    ref = DBLIndex.build(g, n_cap=n, k=16, k_prime=16, max_iters=64)

    mesh = auto_mesh((4, 2), ("data", "model"))
    idx = D.distributed_build(g, mesh, n_cap=n, k=16, k_prime=16,
                              max_iters=64)
    for name in ("dl_in", "dl_out", "bl_in", "bl_out"):
        a = np.asarray(getattr(ref, name))
        b = np.asarray(getattr(idx, name))
        assert (a == b).all(), f"sharded build diverged on {name}"

    rng = np.random.default_rng(0)
    u = rng.integers(0, n, 4096).astype(np.int32)
    v = rng.integers(0, n, 4096).astype(np.int32)
    verd_ref = np.asarray(ref.label_verdicts(u, v))
    verd_dist = np.asarray(D.distributed_label_verdicts(idx, mesh, u, v))
    assert (verd_ref == verd_dist).all(), "sharded verdicts diverged"

    ns = rng.integers(0, n, 64).astype(np.int32)
    nd = rng.integers(0, n, 64).astype(np.int32)
    ref2 = ref.insert_edges(ns, nd, max_iters=64)
    idx2 = D.distributed_insert(idx, mesh, ns, nd, max_iters=64)
    for name in ("dl_in", "dl_out", "bl_in", "bl_out"):
        a = np.asarray(getattr(ref2, name))
        b = np.asarray(getattr(idx2, name))
        assert (a == b).all(), f"sharded insert diverged on {name}"
    # device-resident contract: the sharded insert must come out in the
    # index sharding scheme (no host round-trip / re-device_put), with the
    # epoch a committed replicated int32 scalar
    want_sh = D.index_shardings(mesh)
    assert idx2.dl_in.sharding == want_sh.dl_in, idx2.dl_in.sharding
    assert idx2.graph.src.sharding == want_sh.graph.src
    assert idx2.packed.dl_in.sharding == want_sh.packed.dl_in
    assert idx2.epoch.dtype == jnp.int32 and int(idx2.epoch) == 1
    # a second batch reuses the cached executable and stays resident
    idx3b = D.distributed_insert(idx2, mesh, nd[:8], ns[:8], max_iters=64)
    assert idx3b.dl_in.sharding == want_sh.dl_in

    # fully-dynamic: sharded tombstone delete + dirty query + rebuild
    del_s, del_d = src[:32], dst[:32]
    refd = ref2.delete_edges(del_s, del_d)
    idxd = idx2.delete_edges(del_s, del_d)
    u2 = rng.integers(0, n, 1024).astype(np.int32)
    v2 = rng.integers(0, n, 1024).astype(np.int32)
    ad = np.asarray(refd.query(u2, v2, bfs_chunk=128, max_iters=64,
                               driver="host"))
    bd = np.asarray(idxd.query(u2, v2, bfs_chunk=128, max_iters=64,
                               driver="host"))
    assert (ad == bd).all(), "sharded dirty query diverged"
    refr = refd.rebuild(max_iters=64)
    br = np.asarray(refr.query(u2, v2, bfs_chunk=128, max_iters=64,
                               driver="host"))
    assert (ad == br).all(), "rebuild changed dirty-mode answers"

    # elastic re-placement: different mesh shape, same results
    mesh2 = auto_mesh((8,), ("data",))
    idx3 = D.shard_index(idx2, mesh2)
    verd3 = np.asarray(D.distributed_label_verdicts(idx3, mesh2, u, v))
    verd2 = np.asarray(ref2.label_verdicts(u, v))
    assert (verd3 == verd2).all(), "elastic re-placement diverged"

    # QueryEngine with query-axis sharding == single-device engine == host
    from repro.launch.sharding import reach_place_index
    eng = QueryEngine(bfs_chunk=128, max_iters=64, mesh=mesh2)
    placed = reach_place_index(ref2, mesh2)
    ans_sharded = eng.run(placed, u, v)
    ans_host = ref2.query(u, v, bfs_chunk=128, max_iters=64, driver="host")
    assert (ans_sharded == np.asarray(ans_host)).all(), \
        "sharded engine diverged from host driver"

    # vertex-sharded layout: label planes row-partitioned over all 8
    # devices (1/8th of the planes per device), served through the
    # all-gather-free engine — bitwise equal to the replicated reference
    from repro.core import planes as PL
    from repro.launch.sharding import reach_vertex_shardings
    vmesh = D.vertex_mesh(8)
    vidx, vplan = D.build_vertex_sharded(g, vmesh, n_cap=n, k=16,
                                         k_prime=16, max_iters=64)
    for name in ("dl_in", "dl_out", "bl_in", "bl_out"):
        a = np.asarray(getattr(ref, name))
        b = np.asarray(getattr(vidx, name))
        assert (a == b).all(), f"vertex-sharded build diverged on {name}"
    # sharding assertions: planes + packed words + leaf masks partitioned
    # along the vertex axis, graph replicated
    plane_sh, vec_sh, rep_sh = reach_vertex_shardings(vmesh)
    assert vidx.dl_in.sharding == plane_sh, vidx.dl_in.sharding
    assert vidx.packed.bl_out.sharding == plane_sh
    assert vidx.bl_sources.sharding == vec_sh
    assert vidx.graph.src.sharding == rep_sh
    assert PL.per_device_label_bytes(vidx) * 8 \
        == PL.per_device_label_bytes(ref)
    veng = QueryEngine(vidx, bfs_chunk=128, max_iters=64, vertex_mesh=vmesh)
    ans_vs = veng.query(u, v)
    ans_ref = ref.query(u, v, bfs_chunk=128, max_iters=64, driver="host")
    assert (ans_vs == np.asarray(ans_ref)).all(), \
        "vertex-sharded engine diverged from host driver"
    # sharded insert keeps the layout and the answers
    veng.insert(ns, nd)
    assert veng.index.dl_in.sharding == plane_sh
    ans_vs2 = veng.query(u, v)
    ans_ref2 = ref2.query(u, v, bfs_chunk=128, max_iters=64, driver="host")
    assert (ans_vs2 == np.asarray(ans_ref2)).all(), \
        "vertex-sharded post-insert query diverged"

    print("MULTIDEVICE_OK")


if __name__ == "__main__":
    main()

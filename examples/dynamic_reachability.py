"""End-to-end driver (the paper's kind: serving): a live reachability service
over a growing graph — interleaved batched queries and edge insertions,
exactly the Fig 4/5 workload, with a B-BFS sanity check.

    PYTHONPATH=src python examples/dynamic_reachability.py \
        [--n 20000] [--rounds 10] [--queries 20000] [--inserts 100]
"""
import argparse
import time

import numpy as np

from repro.baselines import bbfs
from repro.core import DBLIndex, make_graph
from repro.graphs.generators import power_law
from repro.serve.compile_cache import enable_compile_cache
from repro.serve.reach_server import ReachabilityServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--m", type=int, default=120_000)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--queries", type=int, default=20_000)
    ap.add_argument("--inserts", type=int, default=100)
    ap.add_argument("--verify", type=int, default=200,
                    help="verify this many queries per round against B-BFS")
    args = ap.parse_args()
    enable_compile_cache()

    src, dst = power_law(args.n, args.m, seed=0)
    g = make_graph(src, dst, args.n,
                   m_cap=args.m + args.rounds * args.inserts)
    t0 = time.perf_counter()
    idx = DBLIndex.build(g, n_cap=args.n, k=64, k_prime=64, max_iters=64)
    print(f"index built in {time.perf_counter() - t0:.2f}s "
          f"({idx.label_bytes() / 2**20:.1f} MiB labels)")

    server = ReachabilityServer(idx, bfs_chunk=64, max_iters=64)
    rng = np.random.default_rng(1)
    for r in range(args.rounds):
        u = rng.integers(0, args.n, args.queries).astype(np.int32)
        v = rng.integers(0, args.n, args.queries).astype(np.int32)
        ans = server.query(u, v)

        if args.verify:
            ref = bbfs.query(server.index.graph, u[:args.verify],
                             v[:args.verify], n_cap=args.n, chunk=64,
                             max_iters=64)
            assert (ans[:args.verify] == ref).all(), \
                f"round {r}: DBL diverged from B-BFS"

        ns = rng.integers(0, args.n, args.inserts).astype(np.int32)
        nd = rng.integers(0, args.n, args.inserts).astype(np.int32)
        server.insert(ns, nd)
        server.engine.check_saturation()    # syncs the insert round counts
        s = server.stats.as_dict()
        e = server.engine.stats.as_dict()
        print(f"round {r}: {s['queries']} queries served "
              f"(ρ={s['rho']:.3f}), {s['inserts']} edges inserted; "
              f"cumulative: {e['bfs_levels']} BFS levels in "
              f"{e['bfs_dispatches']} dispatches, lanes open "
              f"{e['bfs_open_lane_levels']} of {e['bfs_slot_levels']} "
              f"lane-levels, {e['insert_rounds']} insert rounds")
    es = server.engine_stats()
    print(f"engine: backend={es['backend']}, "
          f"{es['dispatch_shapes']} compiled dispatch shapes, "
          f"{es['bfs_dispatches']} BFS dispatches for "
          f"{es['queries']} queries")
    print("all rounds verified against B-BFS — OK")


if __name__ == "__main__":
    main()

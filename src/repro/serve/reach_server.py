"""Batched reachability serving on a live, fully-dynamic DBL index.

The serving analogue of the paper's query workload: interleaved batches of
queries, edge insertions, and edge deletions against one index.  All query
traffic goes through the device-resident ``QueryEngine`` (fused label phase,
compacted BFS chunks, persistent executables); insertions run the engine's
donated Alg-3 path and bump the snapshot epoch WITHOUT draining in-flight
queries; deletions tombstone edges (dirty mode: verdicts that rest on
positive label evidence downgrade to live-edge BFS) and labels are rebuilt
LAZILY — scheduled when the tombstone ratio crosses a policy threshold,
executed at the next flush/query boundary.

Two serving surfaces:

- synchronous ``query()`` — submit + resolve in one call;
- pipelined ``submit()`` / ``flush()`` — micro-batches accumulate across
  ``insert()`` calls and the flush coalesces their BFS residues across
  snapshot epochs into one dispatch sequence.  ``consistency`` picks the
  answer semantics: ``"as-of-submit"`` (each query answered against the
  exact snapshot it observed — per-lane edge-count cutoffs keep this
  bitwise exact) or ``"latest"`` (still-unknown lanes answered against the
  newest snapshot; label positives are monotone so they never change).

``examples/dynamic_reachability.py`` drives it end to end."""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from repro.core.dbl import DBLIndex
from repro.serve.engine import QueryEngine


@dataclass
class ServeStats:
    queries: int = 0
    label_answered: int = 0
    bfs_answered: int = 0
    inserts: int = 0
    deletes: int = 0
    rebuilds: int = 0
    delta_rebuilds: int = 0
    flushes: int = 0
    query_s: float = 0.0
    insert_s: float = 0.0
    delete_s: float = 0.0
    rebuild_s: float = 0.0
    flush_s: float = 0.0

    def as_dict(self):
        rho = self.label_answered / max(self.queries, 1)
        return {"queries": self.queries, "rho": rho,
                "inserts": self.inserts, "deletes": self.deletes,
                "rebuilds": self.rebuilds,
                "delta_rebuilds": self.delta_rebuilds,
                "flushes": self.flushes,
                "query_s": self.query_s, "insert_s": self.insert_s,
                "delete_s": self.delete_s, "rebuild_s": self.rebuild_s,
                "flush_s": self.flush_s}


class ReachabilityServer:
    """Fully-dynamic serving: ``insert`` (Alg 3, epoch bump, pipeline rides
    across it), ``delete`` (epoch-versioned tombstones + dirty flag, no label
    recomputation — in-flight submits drain first), and a *lazy* label
    rebuild.  ``rebuild_dead_ratio`` is the laziness knob: once tombstones
    exceed that fraction of the LIVE edge count, a rebuild over the live
    edge set is SCHEDULED and executed at the next flush/query boundary
    (not inside the delete call), so delete latency stays O(tombstone mask)
    and rebuild cost amortizes across the whole dirty window.  Set it to
    ``None`` to only ever rebuild explicitly.

    The policy denominator is the live count, NOT the raw edge prefix
    ``m``: ``m`` includes the tombstones themselves, so a prefix-based
    ratio would drift downwards as the dirty window grows, and after a
    ``compact()`` squeezed old tombstones out the same number of fresh
    deletions would trigger at a different point.

    ``rebuild_mode`` is forwarded to ``DBLIndex.rebuild``: the default
    ``"auto"`` lets the index pick the incremental (delta) path whenever
    the invalidation estimate is small — the engine re-binds without
    dispatch-shape churn either way — and fall back to a full Alg-1
    rebuild otherwise."""

    def __init__(self, index: DBLIndex | None, *, bfs_chunk: int = 256,
                 max_iters: int = 256, backend: str = "auto",
                 mesh=None, vertex_mesh=None,
                 engine: QueryEngine | None = None,
                 consistency: str = "as-of-submit",
                 rebuild_dead_ratio: float | None = 0.25,
                 rebuild_mode: str = "auto",
                 flush_policy: str | None = None,
                 flush_deadline_ms: float = 25.0,
                 flush_watermark: int = 256,
                 aot_cache: str | None = None):
        if engine is not None:
            # a supplied engine carries its own configuration; conflicting
            # per-server knobs would be silently ignored, so reject them
            if engine.index is not None and index is not None \
                    and engine.index is not index:
                raise ValueError(
                    "both `index` and an engine with a bound index were "
                    "given; pass one or the other")
            self.engine = engine
            if engine.index is None:
                engine.index = index
        else:
            self.engine = QueryEngine(
                index, bfs_chunk=bfs_chunk, max_iters=max_iters,
                backend=backend, mesh=mesh, vertex_mesh=vertex_mesh,
                consistency=consistency, flush_policy=flush_policy,
                flush_deadline_ms=flush_deadline_ms,
                flush_watermark=flush_watermark)
        if self.engine.index is None:
            raise ValueError("server needs an index (directly or via engine)")
        if aot_cache is not None:
            # cold-start path: hits swap in deserialized executables (no
            # recompilation), misses persist this process's executables
            self.engine.aot_warmup(self.engine.index, aot_cache)
        if rebuild_dead_ratio is not None and not 0 < rebuild_dead_ratio <= 1:
            raise ValueError("rebuild_dead_ratio must be in (0, 1] or None")
        if rebuild_mode not in ("full", "delta", "auto"):
            raise ValueError(f"unknown rebuild mode {rebuild_mode!r}")
        self.rebuild_dead_ratio = rebuild_dead_ratio
        self.rebuild_mode = rebuild_mode
        self.stats = ServeStats()
        self._pending = []
        self._rebuild_due = False

    @property
    def index(self) -> DBLIndex:
        return self.engine.index

    @property
    def epoch(self) -> int:
        return self.engine.epoch

    @property
    def dirty(self) -> bool:
        return self.engine.index.is_dirty

    # ------------------------------------------------------- synchronous
    def query(self, u, v) -> np.ndarray:
        self._maybe_rebuild()
        t = time.perf_counter()
        ans, info = self.engine.query(np.asarray(u, np.int32),
                                      np.asarray(v, np.int32),
                                      return_stats=True)
        self.stats.query_s += time.perf_counter() - t
        self.stats.queries += len(ans)
        self.stats.bfs_answered += info["n_bfs"]
        self.stats.label_answered += len(ans) - info["n_bfs"]
        return ans

    # --------------------------------------------------------- pipelined
    def submit(self, u, v):
        """Enqueue a query micro-batch against the current snapshot epoch;
        the label phase runs now, the BFS residue rides the next flush —
        possibly across intervening ``insert()`` calls."""
        t = time.perf_counter()
        pend = self.engine.submit(self.engine.index,
                                  np.asarray(u, np.int32),
                                  np.asarray(v, np.int32))
        self._pending.append(pend)
        self.stats.query_s += time.perf_counter() - t
        return pend

    def flush(self, *, consistency: str | None = None) -> list:
        """Resolve every outstanding micro-batch in one epoch-coalesced
        dispatch sequence; returns their answers in submission order.
        A scheduled lazy rebuild runs here, after the resolution."""
        t = time.perf_counter()
        # flush BEFORE clearing the queue: if the engine rejects the
        # consistency mode, the submitted batches must stay enqueued
        pending = self._pending
        outs = self.engine.flush(pending, consistency=consistency)
        self._pending = []
        self.stats.flush_s += time.perf_counter() - t
        self.stats.flushes += 1
        for pend, ans in zip(pending, outs):
            nu = min(int(pend.n_unknown), pend.q)
            self.stats.queries += len(ans)
            self.stats.bfs_answered += nu
            self.stats.label_answered += len(ans) - nu
        self._maybe_rebuild()
        return outs

    def poll(self) -> bool:
        """Adaptive-flush poll point: give the engine's flush policy a
        chance to resolve the pipeline (a latency deadline must be able to
        fire without new traffic arriving).  Returns True when the policy
        flushed.  No-op without a policy."""
        return self.engine.maybe_flush()

    def insert(self, src, dst):
        """Alg-3 insert: bumps the snapshot epoch; outstanding submits stay
        in flight and resolve with exact as-of-submit cutoffs at flush."""
        t = time.perf_counter()
        idx = self.engine.insert(np.asarray(src, np.int32),
                                 np.asarray(dst, np.int32))
        idx.packed.dl_in.block_until_ready()
        self.stats.insert_s += time.perf_counter() - t
        self.stats.inserts += len(np.asarray(src))

    # ------------------------------------------------------ fully dynamic
    def delete(self, src, dst):
        """Tombstone matching live edges and go dirty — O(mask) work, no
        label recomputation.  Drains in-flight submits (see engine.delete),
        then *schedules* a lazy rebuild if the tombstone ratio crossed the
        policy threshold; the rebuild itself runs at the next flush/query
        boundary so the delete call returns immediately."""
        from repro.core import graph as G
        t = time.perf_counter()
        idx = self.engine.delete(np.asarray(src, np.int32),
                                 np.asarray(dst, np.int32))
        idx.graph.del_at.block_until_ready()
        self.stats.delete_s += time.perf_counter() - t
        self.stats.deletes += len(np.asarray(src))
        if self.rebuild_dead_ratio is not None and not self._rebuild_due:
            dead = int(np.asarray(G.dead_edge_count(idx.graph)))
            live = max(int(np.asarray(idx.graph.m)) - dead, 1)
            if dead / live >= self.rebuild_dead_ratio:
                self._rebuild_due = True

    def rebuild(self, **build_kw):
        """Rebuild labels over the live edge set now (clears dirty state;
        compacts tombstones; re-binds the engine, resolving in-flight
        submits first).  Defaults to the server's ``rebuild_mode`` policy
        ("auto": the index picks delta vs full by invalidation estimate)."""
        build_kw.setdefault("mode", self.rebuild_mode)
        t = time.perf_counter()
        idx = self.engine.rebuild(**build_kw)
        idx.packed.dl_in.block_until_ready()
        self.stats.rebuild_s += time.perf_counter() - t
        self.stats.rebuilds += 1
        if self.engine.last_rebuild_info["mode"] == "delta":
            self.stats.delta_rebuilds += 1
        self._rebuild_due = False
        # queued pendings were resolved by the re-bind drain; they stay in
        # the queue so the next flush() still returns their answers in order
        return idx

    def _maybe_rebuild(self):
        if self._rebuild_due:
            self.rebuild()

    def engine_stats(self) -> dict:
        """Engine-level telemetry: dispatch shapes + batch/BFS counters."""
        d = self.engine.stats.as_dict()
        d["dispatch_shapes"] = self.engine.dispatch_shapes()
        d["backend"] = self.engine.backend
        d["epoch"] = self.engine.epoch
        d["consistency"] = self.engine.consistency
        d["dirty"] = self.dirty
        d["rebuild_due"] = self._rebuild_due
        d["rebuild_mode"] = self.rebuild_mode
        d["last_rebuild"] = self.engine.last_rebuild_info
        d["layout"] = self.engine.layout
        d["flush_policy"] = self.engine.flush_policy
        # halo-exchange accounting (all-zero on replicated engines):
        # halo_stats() syncs the telemetry and mirrors the headline
        # counters into stats, so as_dict() above may be one flush stale —
        # overwrite with the freshly drained numbers
        halo = self.engine.halo_stats()
        d["halo"] = {**halo, "mode": self.engine.halo_mode,
                     "hub_count": self.engine.hub_count}
        d.update({k: halo[k] for k in
                  ("halo_bytes", "halo_rounds", "quiet_pair_rounds")})
        if self.engine.aot_cache is not None:
            d["aot"] = {"hits": self.engine.aot_cache.hits,
                        "misses": self.engine.aot_cache.misses,
                        "stores": self.engine.aot_cache.stores}
        return d


def main(argv=None):
    """Tiny serving driver: build an index over a generated power-law
    graph, run an interleaved query/insert/delete stream, print stats.

    ``--aot-cache DIR`` round-trips the engine's verdict + BFS-bucket
    executables through a ``jax.export`` disk cache — run twice with the
    same flags and the second cold start compiles nothing (watch the
    ``aot`` hit counters).  ``--vertex-shards N`` serves with
    vertex-sharded label planes (requires >= N devices)."""
    import argparse
    import json

    import numpy as np

    from repro.graphs.generators import power_law

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--m", type=int, default=16384)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--aot-cache", default=None,
                    help="directory for jax.export'd executables; cold "
                         "starts with a warm cache skip recompilation")
    ap.add_argument("--flush-policy", default=None,
                    choices=["deadline", "watermark"])
    ap.add_argument("--vertex-shards", type=int, default=0,
                    help="serve with vertex-sharded label planes over this "
                         "many devices (0 = replicated)")
    a = ap.parse_args(argv)

    from repro.serve.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.core.dbl import DBLIndex
    from repro.core.graph import make_graph
    src, dst = power_law(a.n, a.m, seed=0)
    g = make_graph(src, dst, a.n, m_cap=a.m + a.rounds * 64)
    idx = DBLIndex.build(g, n_cap=a.n, k=a.k, k_prime=a.k)
    vmesh = None
    if a.vertex_shards:
        from repro.core.distributed import vertex_mesh
        vmesh = vertex_mesh(a.vertex_shards)
    t0 = time.perf_counter()
    srv = ReachabilityServer(idx, backend=a.backend, vertex_mesh=vmesh,
                             flush_policy=a.flush_policy,
                             aot_cache=a.aot_cache)
    rng = np.random.default_rng(0)
    for r in range(a.rounds):
        u = rng.integers(0, a.n, a.batch).astype(np.int32)
        v = rng.integers(0, a.n, a.batch).astype(np.int32)
        srv.submit(u, v)
        if r % 2:
            srv.insert(rng.integers(0, a.n, 64).astype(np.int32),
                       rng.integers(0, a.n, 64).astype(np.int32))
        srv.poll()
    srv.flush()
    print(json.dumps({"wall_s": time.perf_counter() - t0,
                      **srv.stats.as_dict(),
                      "engine": srv.engine_stats()}, indent=2, default=str))


if __name__ == "__main__":
    main()

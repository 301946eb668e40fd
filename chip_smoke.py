"""Run the served DBL path once on a TPU, on the Wiki graph of Table 2.

    python chip_smoke.py              # one chip: the replicated layout
    python chip_smoke.py --chips 4    # four chips: the vertex-sharded layout
                                      # against the replicated engine

The graph is the Wiki row of the paper's Table 2 (published size 2.4M
vertices, 5.0M edges), generated from ``--seed`` by
``graphs.generators.dag_like`` with its degree and back-edge share, and
indexed with k = k' = 64.  Its scale is cut to fit the run's time limit
(see ``ONE_CHIP_SCALE``).  One process drives the normal entry points —
``DBLIndex.build`` and ``ReachabilityServer`` over the engine's compiled
Pallas kernels — through three phases:

1. build the index (``check="raise"``: a saturated build fails the run);
2. serve several ~16k-query batches through ``submit``/``flush`` with ~1k-edge
   insert batches in between, so one flush coalesces BFS residues across
   snapshot epochs;
3. delete ~1k edges, query while the index is dirty, rebuild with
   ``mode="auto"`` and query again.

Half of every batch is uniform pairs, half ``(u, w)`` with ``w`` at the end
of a short random walk from ``u``, so positives occur.  A few hundred
answers per phase are compared with a host BFS over a numpy CSR of the
edges live at the batch's snapshot, and every verdict route (DL positive,
BL negative, BFS residue) must have answered some lane.  ``--chips 4``
runs only the vertex-sharded engine over the same stream beside the
replicated one: answers must be bitwise equal, and every label plane must
sit on four devices at a quarter of its bytes on each.

Without a TPU the script exits non-zero and prints no result.  Lines before
the last are diagnostics (phase wall times are smoke timings, not
benchmark numbers); the last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import distributed as D  # noqa: E402
from repro.core.dbl import DBLIndex  # noqa: E402
from repro.core.graph import make_graph  # noqa: E402
from repro.graphs.generators import dag_like  # noqa: E402
from repro.serve.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve.engine import QueryEngine  # noqa: E402
from repro.serve.reach_server import ReachabilityServer  # noqa: E402

#: Table 2 "Wiki": 2.4M vertices / 5.0M edges, sparse and poorly connected
WIKI_N, WIKI_M, WIKI_BACK_FRAC = 2_400_000, 5_000_000, 0.05
K = 64

#: Fractions of the Wiki graph's published size (vertices and edges alike)
#: that each run serves by default; ``--scale`` overrides them.  At the
#: published size one v5e took 402 s to build, each insert batch took
#: over 80 s, and the stream had not finished its rebuild after 880 s, so
#: it does not fit the 1,200-s limit.  At 0.83 of the size (4.15M edges)
#: the whole run took about 400 s with an empty compile cache; the label
#: fixpoints slow down several-fold somewhere between 4.15M and 5.0M edges
#: (PERF.md), and 0.83 is the largest fraction tried below that.  The
#: four-chip check compares layouts, whose equality does not depend on the
#: size; it runs 1/64 of the size, because four chips cost four times as
#: much per second.
ONE_CHIP_SCALE, FOUR_CHIP_SCALE = 0.83, 1 / 64

#: the query/insert/delete stream both runs serve
STREAM = dict(rounds=3, batch=16_384, insert_batch=1024, delete_batch=1024,
              dirty_batch=4096, checks=256)


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(*parts):
    print(*parts, flush=True)


# ------------------------------------------------------------ host side
class HostGraph:
    """The stream's edge list kept on the host — the reference the answers
    are checked against, independent of the index and the engine."""

    def __init__(self, n: int, src, dst):
        self.n = n
        self.src = np.asarray(src, np.int32)
        self.dst = np.asarray(dst, np.int32)
        self.live = np.ones(self.src.size, bool)

    def insert(self, s, d):
        self.src = np.concatenate([self.src, s])
        self.dst = np.concatenate([self.dst, d])
        self.live = np.concatenate([self.live, np.ones(s.size, bool)])

    def delete(self, s, d):
        """Tombstone every live edge matching a pair (the index's rule)."""
        key = self.src.astype(np.int64) * self.n + self.dst
        gone = np.isin(key, s.astype(np.int64) * self.n + d)
        self.live &= ~gone

    def snapshot(self):
        """(src, dst) of the live edges now — an as-of reference."""
        return self.src[self.live], self.dst[self.live]


def walk_targets(src, dst, n, starts, rng, max_len: int = 8):
    """End points of random walks of 1..max_len steps over (src, dst)."""
    order = np.argsort(src, kind="stable")
    heads = dst[order]
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    cur = starts.copy()
    steps = rng.integers(1, max_len + 1, starts.size)
    for s in range(max_len):
        deg = indptr[cur + 1] - indptr[cur]
        pick = indptr[cur] + (rng.random(starts.size) * deg).astype(np.int64)
        nxt = heads[np.minimum(pick, heads.size - 1)]
        cur = np.where((deg > 0) & (steps > s), nxt, cur)
    return cur.astype(np.int32)


def host_reach(src, dst, n, us, ws):
    """(len(us),) bool: is ws[i] reachable from us[i] over (src, dst)?  One
    level-synchronous BFS per pair over a numpy CSR, touching only the
    frontier's out-edges and stopping once the target is seen (u reaches
    itself)."""
    order = np.argsort(src, kind="stable")
    heads = dst[order]
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    out = np.zeros(us.size, bool)
    for i, (u, w) in enumerate(zip(us, ws)):
        seen = np.zeros(n, bool)
        seen[u] = True
        frontier = np.array([u], np.int64)
        while frontier.size and not seen[w]:
            first = indptr[frontier]
            deg = indptr[frontier + 1] - first
            total = int(deg.sum())
            if total == 0:
                break
            # positions of every frontier vertex's out-edges in ``heads``
            at = np.repeat(first - np.cumsum(deg) + deg, deg) \
                + np.arange(total)
            nxt = heads[at]
            nxt = np.unique(nxt[~seen[nxt]])
            seen[nxt] = True
            frontier = nxt
        out[i] = seen[w]
    return out


# ------------------------------------------------------------ the stream
class Stream:
    """Drives one or more servers through the same operations in lockstep
    and checks them: every server's answers bitwise equal to the first's,
    and a sample of the first's equal to the host BFS."""

    def __init__(self, servers, host: HostGraph, *, seed: int, checks: int):
        self.servers = servers
        self.host = host
        self.rng = np.random.default_rng(seed)
        self.checks = checks

    def queries(self, size: int):
        n = self.host.n
        half = size // 2
        u = self.rng.integers(0, n, size).astype(np.int32)
        v = self.rng.integers(0, n, size).astype(np.int32)
        src, dst = self.host.snapshot()
        v[half:] = walk_targets(src, dst, n, u[half:], self.rng)
        return u, v

    def new_edges(self, size: int):
        n = self.host.n
        s = self.rng.integers(0, n, size).astype(np.int32)
        d = self.rng.integers(0, n - 1, size).astype(np.int32)
        return s, d + (d >= s)          # no self-loops

    def verify(self, phase: str, u, v, answers, ref_edges):
        for i, a in enumerate(answers[1:], 1):
            check(np.array_equal(a, answers[0]),
                  f"{phase}: server {i} answers differ from server 0 in "
                  f"{int((a != answers[0]).sum())} of {a.size} lanes")
        half = u.size // 2
        take = np.concatenate([
            self.rng.choice(half, self.checks // 2, replace=False),
            half + self.rng.choice(u.size - half, self.checks // 2,
                                   replace=False)])
        t = time.perf_counter()
        want = host_reach(*ref_edges, self.host.n, u[take], v[take])
        got = answers[0][take]
        bad = int((got != want).sum())
        check(bad == 0, f"{phase}: {bad} of {take.size} sampled answers "
                        "differ from the host BFS")
        log(f"{phase}: {take.size} sampled answers match the host BFS "
            f"({int(want.sum())} reachable; host BFS "
            f"{time.perf_counter() - t:.3f} s)")

    def serve_rounds(self, rounds: int, batch: int, insert_batch: int):
        """Phase 2: submit, insert, submit, ... flush — the flush pools the
        BFS residues of every round's snapshot epoch."""
        subs = []
        for r in range(rounds):
            u, v = self.queries(batch)
            for srv in self.servers:
                srv.submit(u, v)
            subs.append((u, v, self.host.snapshot()))
            s, d = self.new_edges(insert_batch)
            timed(f"insert {insert_batch} edges",
                  lambda: [srv.insert(s, d) for srv in self.servers])
            self.host.insert(s, d)
        outs = timed("coalesced flush",
                     lambda: [srv.flush() for srv in self.servers])
        for r, (u, v, ref) in enumerate(subs):
            self.verify(f"serve round {r}", u, v, [o[r] for o in outs], ref)

    def query(self, phase: str, batch: int):
        u, v = self.queries(batch)
        answers = []
        for srv in self.servers:
            srv.submit(u, v)
            answers.append(srv.flush()[0])
        self.verify(phase, u, v, answers, self.host.snapshot())

    def delete(self, size: int):
        src, dst = self.host.snapshot()
        pick = self.rng.choice(src.size, size, replace=False)
        s, d = src[pick], dst[pick]
        for srv in self.servers:
            srv.delete(s, d)
        self.host.delete(s, d)

    def rebuild(self):
        infos = []
        for srv in self.servers:
            srv.rebuild(mode="auto")
            infos.append(srv.engine.last_rebuild_info)
        return infos


def timed(label: str, fn):
    t = time.perf_counter()
    out = fn()
    log(f"smoke timing: {label} {time.perf_counter() - t:.3f} s")
    return out


def run_stream(stream: Stream, *, rounds: int, batch: int,
               insert_batch: int, delete_batch: int, dirty_batch: int):
    timed("phase 2: serve rounds + coalesced flush",
          lambda: stream.serve_rounds(rounds, batch, insert_batch))
    timed(f"phase 3: delete {delete_batch} edges",
          lambda: stream.delete(delete_batch))
    for srv in stream.servers:
        check(srv.dirty, "the index is not dirty after a delete batch")
    timed("phase 3: query while dirty",
          lambda: stream.query("dirty", dirty_batch))
    infos = timed("phase 3: rebuild(mode='auto')", stream.rebuild)
    log("rebuild:", json.dumps(infos, default=str))
    for srv in stream.servers:
        check(not srv.dirty, "the index is still dirty after a rebuild")
    timed("phase 3: query after rebuild",
          lambda: stream.query("rebuilt", batch))


def wiki_edges(n: int, m: int, seed: int):
    return dag_like(n, m, seed=seed, back_frac=WIKI_BACK_FRAC)


def smoke_one_chip(*, n, m, seed, backend, rounds, batch, insert_batch,
                   delete_batch, dirty_batch, checks) -> dict:
    """Phases 1-3 on the replicated layout; returns the engine stats."""
    src, dst = timed("generate graph", lambda: wiki_edges(n, m, seed))
    g = make_graph(src, dst, n, m_cap=m + rounds * insert_batch)
    idx = timed("phase 1: build", lambda: jax.block_until_ready(
        DBLIndex.build(g, n_cap=n, k=K, k_prime=K, check="raise")))
    del g
    srv = ReachabilityServer(idx, backend=backend, rebuild_mode="auto")
    eng = srv.engine
    check(eng.backend == backend,
          f"engine backend {eng.backend!r}, expected {backend!r}")
    log(f"engine: backend={eng.backend} donate={eng.donate} "
        f"q_block={eng.q_block} bfs_chunk={eng.bfs_chunk}; index on "
        f"{sorted({str(d) for d in idx.packed.dl_in.devices()})}")
    # the label phase must carry the compiled kernel, not the interpreter
    uj = jnp.zeros(batch, jnp.int32)
    lowered = eng._label_phase.lower(idx.packed, idx.il, uj, uj,
                                     idx.dirty_flag).as_text()
    if backend == "pallas":
        check("tpu_custom_call" in lowered, "the label phase holds no "
              "compiled Pallas kernel (tpu_custom_call)")
    del idx, uj
    stream = Stream([srv], HostGraph(n, src, dst), seed=seed + 1,
                    checks=checks)
    run_stream(stream, rounds=rounds, batch=batch, insert_batch=insert_batch,
               delete_batch=delete_batch, dirty_batch=dirty_batch)
    stats = srv.engine_stats()
    hits = stats["prune_hits"]
    log("engine_stats:", json.dumps(stats, default=str))
    log("prune_hits:", json.dumps(hits))
    for route in ("dl", "bl", "bfs"):
        check(hits[route] > 0, f"no lane was answered by the {route!r} "
                               "route")
    check(sum(hits.values()) == stats["queries"],
          "prune_hits do not partition the queries")
    return stats


def _plane_bytes_per_device(x) -> dict:
    out = {}
    for shard in x.addressable_shards:
        out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def smoke_four_chips(*, n, m, seed, rounds, batch, insert_batch,
                     delete_batch, dirty_batch, checks, shards: int = 4):
    """The vertex-sharded engine beside the replicated one on one stream."""
    m_cap = m + rounds * insert_batch
    src, dst = timed("generate graph", lambda: wiki_edges(n, m, seed))
    mesh = D.vertex_mesh(shards)
    # each layout builds from its own upload of the edges: the replicated
    # engine donates its graph buffers on insert
    sharded, _ = timed("build (vertex-sharded)", lambda: jax.block_until_ready(
        D.build_vertex_sharded(make_graph(src, dst, n, m_cap=m_cap), mesh,
                               n_cap=n, k=K, k_prime=K, check="raise")))
    planes = {"dl_in": sharded.dl_in, "dl_out": sharded.dl_out,
              "bl_in": sharded.bl_in, "bl_out": sharded.bl_out,
              **{f"packed.{f}": getattr(sharded.packed, f)
                 for f in sharded.packed._fields}}
    for name, x in planes.items():
        per_dev = _plane_bytes_per_device(x)
        check(len(per_dev) == shards,
              f"{name} sits on {len(per_dev)} devices, expected {shards}")
        check(all(b * shards == x.nbytes for b in per_dev.values()),
              f"{name}: per-device bytes {sorted(per_dev.values())} are not "
              f"1/{shards} of {x.nbytes}")
    log(f"plane placement: {len(planes)} planes each on {shards} distinct "
        f"devices at 1/{shards} of their bytes "
        f"(dl_in {sharded.dl_in.nbytes // shards} of "
        f"{sharded.dl_in.nbytes} bytes per device)")
    replicated = timed("build (replicated)", lambda: jax.block_until_ready(
        DBLIndex.build(make_graph(src, dst, n, m_cap=m_cap), n_cap=n, k=K,
                       k_prime=K, check="raise")))
    check(np.array_equal(np.asarray(sharded.packed.dl_in),
                         np.asarray(replicated.packed.dl_in))
          and np.array_equal(np.asarray(sharded.packed.bl_out),
                             np.asarray(replicated.packed.bl_out)),
          "vertex-sharded labels differ from the replicated build")
    servers = [ReachabilityServer(replicated, rebuild_mode="auto"),
               ReachabilityServer(None, rebuild_mode="auto",
                                  engine=QueryEngine(sharded,
                                                     vertex_mesh=mesh))]
    check(servers[1].engine.layout == "vertex_sharded",
          "the second engine is not vertex-sharded")
    del replicated, sharded
    stream = Stream(servers, HostGraph(n, src, dst), seed=seed + 1,
                    checks=checks)
    run_stream(stream, rounds=rounds, batch=batch, insert_batch=insert_batch,
               delete_batch=delete_batch, dirty_batch=dirty_batch)
    for name, srv in zip(("replicated", "vertex_sharded"), servers):
        log(f"engine_stats {name}:",
            json.dumps(srv.engine_stats(), default=str))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the replicated layout on one chip; 4: the "
                         "vertex-sharded layout against the replicated one")
    ap.add_argument("--scale", type=float, default=None,
                    help="fraction of the Wiki graph's published size "
                         f"(default {ONE_CHIP_SCALE} on one chip, "
                         f"{FOUR_CHIP_SCALE} on four)")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    scale = a.scale if a.scale is not None else (
        ONE_CHIP_SCALE if a.chips == 1 else FOUR_CHIP_SCALE)
    if not 0 < scale <= 1:
        ap.error(f"--scale {scale} is not in (0, 1]")

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < a.chips:
        print(f"chip_smoke: --chips {a.chips} needs {a.chips} devices, JAX "
              f"finds {len(devices)}", file=sys.stderr)
        return 2
    log("compile cache:", enable_compile_cache())
    sizes = dict(n=round(WIKI_N * scale), m=round(WIKI_M * scale),
                 seed=a.seed, **STREAM)
    log(f"device: {devices[0].device_kind} x {len(devices)}; graph: Wiki x "
        f"{scale:g} n={sizes['n']} m={sizes['m']}, k=k'={K}, seed {a.seed}")
    t0 = time.perf_counter()
    if a.chips == 1:
        smoke_one_chip(backend="pallas", **sizes)
    else:
        smoke_four_chips(shards=a.chips, **sizes)
    log(f"smoke timing: total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

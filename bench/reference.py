"""The yardstick's own graph code: the generator, the traffic walks and the
plain reference the answers are checked against.

Copied from the program (``repro.graphs.generators.dag_like`` and
``chip_smoke.py``'s ``walk_targets``, here over a ``csr`` given to it;
``host_reach`` is ``chip_smoke.py``'s per-pair BFS made bidirectional) so
that a change to the program cannot change what the benchmark generates or
how it judges.  Imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

#: delete version of an edge that is never deleted
NEVER = np.iinfo(np.int64).max


def dag_like(n: int, m: int, *, seed: int, back_frac: float):
    """Mostly-forward random edges with a ``back_frac`` share of back
    edges, so that cycles and SCC merges occur (the sparse, poorly
    connected regime of the Email/Wiki rows of the DBL paper's Table 2)."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, n, size=m, dtype=np.int32)
    b = rng.integers(0, n, size=m, dtype=np.int32)
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    eq = lo == hi
    hi[eq] = (hi[eq] + 1) % n
    lo[eq] = np.minimum(lo[eq], hi[eq])
    back = rng.random(m) < back_frac
    src = np.where(back, hi, lo)
    dst = np.where(back, lo, hi)
    return src.astype(np.int32), dst.astype(np.int32)


def csr(src, dst, n: int):
    """(indptr, heads): out-edges of every vertex, sorted by source."""
    order = np.argsort(src, kind="stable")
    heads = dst[order]
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, src + 1, 1)
    return np.cumsum(indptr), heads


def walk_targets(indptr, heads, starts, rng, max_len: int = 8):
    """End points of random walks of 1..max_len steps over the graph whose
    out-edges ``csr`` gives; a walk that reaches a vertex with no out-edge
    stops there."""
    cur = starts.copy()
    steps = rng.integers(1, max_len + 1, starts.size)
    for s in range(max_len):
        deg = indptr[cur + 1] - indptr[cur]
        pick = indptr[cur] + (rng.random(starts.size) * deg).astype(np.int64)
        nxt = heads[np.minimum(pick, heads.size - 1)]
        cur = np.where((deg > 0) & (steps > s), nxt, cur)
    return cur.astype(np.int32)


def _out_edges(indptr, heads, frontier):
    """Heads of every out-edge of ``frontier``'s vertices."""
    first = indptr[frontier]
    deg = indptr[frontier + 1] - first
    total = int(deg.sum())
    at = np.repeat(first - np.cumsum(deg) + deg, deg) + np.arange(total)
    return heads[at]


def host_reach(src, dst, n: int, us, ws):
    """(len(us),) bool: is ws[i] reachable from us[i] over (src, dst)?  One
    bidirectional level-synchronous BFS per pair over numpy CSRs: a
    forward search from u and a backward one from w, expanding the smaller
    frontier each step.  Reachable once a new vertex of one side has been
    seen by the other; unreachable once either frontier runs dry, since
    that side's closure is then complete (u reaches itself)."""
    fwd = csr(src, dst, n)
    bwd = csr(dst, src, n)
    seen = (np.zeros(n, np.int64), np.zeros(n, np.int64))   # stamp per pair
    out = np.zeros(len(us), bool)
    for i, (u, w) in enumerate(zip(us, ws)):
        stamp = i + 1
        if u == w:
            out[i] = True
            continue
        seen[0][u] = seen[1][w] = stamp
        fronts = [np.array([u], np.int64), np.array([w], np.int64)]
        while fronts[0].size and fronts[1].size:
            side = 0 if fronts[0].size <= fronts[1].size else 1
            nxt = _out_edges(*(fwd, bwd)[side], fronts[side])
            nxt = np.unique(nxt[seen[side][nxt] != stamp])
            if (seen[1 - side][nxt] == stamp).any():
                out[i] = True
                break
            seen[side][nxt] = stamp
            fronts[side] = nxt
    return out


class EdgeLog:
    """Every edge the run ever held, with the version that inserted it and
    the version that deleted it.  Version ``v`` is the graph after the
    ``v``-th update; the initial graph is version 0.  A delete tombstones
    every live edge that matches one of its pairs, as the index does."""

    def __init__(self, n: int, src, dst):
        self.n = n
        self.src = np.asarray(src, np.int32)
        self.dst = np.asarray(dst, np.int32)
        self.ins = np.zeros(self.src.size, np.int64)
        self.dele = np.full(self.src.size, NEVER, np.int64)
        self.version = 0
        self._by_src = None     # every edge's slot, stably sorted by source

    def live_mask(self, version: int | None = None):
        v = self.version if version is None else version
        return (self.ins <= v) & (self.dele > v)

    def snapshot(self, version: int | None = None):
        """(src, dst) of the edges live at ``version`` (default: now)."""
        live = self.live_mask(version)
        return self.src[live], self.dst[live]

    def out_csr(self):
        """``csr`` of the edges live now, without a sort: the slots of
        every edge the log holds, stably sorted by source once and kept so
        as edges are inserted, less the dead ones."""
        if self._by_src is None:
            self._by_src = np.argsort(self.src, kind="stable")
        order = self._by_src[self.live_mask()[self._by_src]]
        indptr = np.zeros(self.n + 1, np.int64)
        indptr[1:] = np.cumsum(np.bincount(self.src[order],
                                           minlength=self.n))
        return indptr, self.dst[order]

    def insert(self, s, d) -> int:
        if self._by_src is not None:
            s = np.asarray(s, np.int32)
            new = np.argsort(s, kind="stable")
            at = np.searchsorted(self.src[self._by_src], s[new], "right")
            self._by_src = np.insert(self._by_src, at, self.src.size + new)
        self.version += 1
        self.src = np.concatenate([self.src, np.asarray(s, np.int32)])
        self.dst = np.concatenate([self.dst, np.asarray(d, np.int32)])
        self.ins = np.concatenate([self.ins, np.full(len(s), self.version)])
        self.dele = np.concatenate([self.dele, np.full(len(s), NEVER)])
        return self.version

    def delete(self, s, d) -> int:
        self.version += 1
        key = self.src.astype(np.int64) * self.n + self.dst
        gone = np.isin(key, np.asarray(s, np.int64) * self.n + d) \
            & self.live_mask()
        self.dele[gone] = self.version
        return self.version

    def reach(self, version: int, us, ws):
        """Reference answers for pairs (us, ws) as of ``version``."""
        src, dst = self.snapshot(version)
        return host_reach(src, dst, self.n, np.asarray(us), np.asarray(ws))

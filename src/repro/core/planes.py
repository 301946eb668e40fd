"""PlaneStore — label-plane storage with an explicit layout, and the
all-gather-free collectives behind the vertex-sharded layout.

Every DBL lifecycle path (Alg-1 build, Alg-3 insert, tombstone delete,
delta/full rebuild, Alg-2 query) reads and writes the same four bool planes
(DL-in/out, BL-in/out) plus their seed metadata (the landmark vector and the
BL leaf masks).  Historically each path manipulated the raw arrays by hand;
this module centralizes that state as a :class:`PlaneStore` that

- owns the planes + ``landmarks`` + ``bl_sources``/``bl_sinks``;
- knows its **layout** — ``"replicated"`` (every device holds every row; the
  historical behavior) or ``"vertex_sharded"`` (rows partitioned into
  contiguous blocks along a 1-axis mesh named ``"vertex"``, so per-device
  label bytes shrink by the shard count — the route past one device's HBM);
- exposes the row/column/seed-reset operations the lifecycle paths used to
  do by hand: Alg-1 seed construction, fused-plane assembly/splitting, the
  delta rebuild's dirty-row ∪ fresh-column reset, insert seed scattering,
  and packing.

The vertex-sharded layout never materializes a full plane on any device:

- **fixpoints** (`halo_propagate`) run on shard-local rows.  Edges are
  partitioned by the *receiving* endpoint's owner (one padded edge bucket
  per shard, built host-side by :func:`shard_plan`); each relaxation round
  exchanges only the **boundary frontier rows** — label rows of
  frontier-active vertices that sit on a cut edge — via one
  ``all_to_all`` over a precomputed halo routing table.  Non-frontier
  boundary rows travel as zeros, which are no-ops under the OR monoid, so
  the per-round traffic is O(cut × lanes), never O(n_cap × lanes): there is
  no label all-gather anywhere in the fixpoint.
- **verdicts** (`sharded_rows`) are all-gather-free by construction: Alg 2
  only reads eight (Q, W) *row blocks* (``core.query.RowBlocks``), so each
  shard contributes the rows it owns (zeros elsewhere) and a single
  ``psum`` per batch reconstructs the blocks everywhere — O(Q·W) traffic.
- **BFS residues** (`sharded_pruned_bfs`) keep the (n_cap, Qc) frontier,
  visited, and admit planes row-sharded and exchange only boundary frontier
  *bits* per round, reducing per-lane hits with the same single-collective
  discipline.

Bitwise equivalence with the replicated path is a contract, not an
aspiration: every sharded op mirrors its replicated twin's round structure
exactly (same seeds, same frontier evolution, same monotone reductions), so
labels, verdicts, and BFS hits are identical bit-for-bit —
``tests/test_sharded_planes.py`` pins this differentially across the whole
lifecycle on a forced-multi-device CPU mesh.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import bitset
from . import query as Q
from .propagate import _INT_MAX, check_halo_mode, check_plane_repr
from .select import leaf_hash

#: the mesh axis vertex-sharded planes are partitioned along
VERTEX_AXIS = "vertex"


# --------------------------------------------------------------- layout
@dataclasses.dataclass(frozen=True)
class PlaneLayout:
    """Static (hashable) layout descriptor — jit-cache-key material."""
    kind: str = "replicated"          # "replicated" | "vertex_sharded"
    axis: str = VERTEX_AXIS
    shards: int = 1

    def __post_init__(self):
        if self.kind not in ("replicated", "vertex_sharded"):
            raise ValueError(f"unknown plane layout {self.kind!r}")
        if self.kind == "replicated" and self.shards != 1:
            raise ValueError("replicated layout has exactly one shard")

    @property
    def sharded(self) -> bool:
        return self.kind == "vertex_sharded"


REPLICATED = PlaneLayout()


def vertex_layout(mesh: Mesh) -> PlaneLayout:
    """Layout for a 1-axis vertex mesh."""
    if len(mesh.axis_names) != 1:
        raise ValueError("vertex-sharded planes need a 1-axis mesh, got "
                         f"axes {mesh.axis_names}")
    return PlaneLayout("vertex_sharded", mesh.axis_names[0],
                       int(mesh.devices.size))


def layout_of(plane) -> PlaneLayout:
    """Derive the layout a plane actually has from its device placement:
    rows partitioned along a (>1-device) mesh axis => vertex_sharded."""
    sh = getattr(plane, "sharding", None)
    if isinstance(sh, NamedSharding) and len(sh.spec) and sh.spec[0]:
        ax = sh.spec[0]
        ax = ax[0] if isinstance(ax, tuple) else ax
        size = int(np.prod([sh.mesh.shape[a] for a in
                            (sh.spec[0] if isinstance(sh.spec[0], tuple)
                             else (sh.spec[0],))]))
        if size > 1:
            return PlaneLayout("vertex_sharded", str(ax), size)
    return REPLICATED


def _check_rows(n_cap: int, layout: PlaneLayout) -> int:
    if n_cap % layout.shards:
        raise ValueError(f"n_cap={n_cap} must divide evenly into "
                         f"{layout.shards} vertex shards")
    return n_cap // layout.shards


# ----------------------------------------------------------- PlaneStore
@jax.tree_util.register_pytree_node_class
class PlaneStore:
    """The four label planes + seed metadata, with a static layout.

    A pytree whose children are the arrays and whose aux data is the
    :class:`PlaneLayout` — so jitted consumers specialize per layout, and
    ``jax.tree`` surgery (device_put, donation, checkpointing) sees exactly
    the label state.  ``DBLIndex.store`` builds one as a zero-copy view of
    the index's flat fields; ``as_fields()`` goes back.
    """

    __slots__ = ("dl_in", "dl_out", "bl_in", "bl_out",
                 "landmarks", "bl_sources", "bl_sinks", "layout")

    def __init__(self, dl_in, dl_out, bl_in, bl_out, landmarks,
                 bl_sources, bl_sinks, layout: PlaneLayout = REPLICATED):
        self.dl_in = dl_in
        self.dl_out = dl_out
        self.bl_in = bl_in
        self.bl_out = bl_out
        self.landmarks = landmarks
        self.bl_sources = bl_sources
        self.bl_sinks = bl_sinks
        self.layout = layout

    def tree_flatten(self):
        return ((self.dl_in, self.dl_out, self.bl_in, self.bl_out,
                 self.landmarks, self.bl_sources, self.bl_sinks),
                self.layout)

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(*children, layout=layout)

    # ---- shape helpers --------------------------------------------------
    @property
    def n_cap(self) -> int:
        return self.dl_in.shape[0]

    @property
    def k(self) -> int:
        return self.dl_in.shape[1]

    @property
    def k_prime(self) -> int:
        return self.bl_in.shape[1]

    # ---- seed construction (Alg 1 line 1) -------------------------------
    @staticmethod
    def seeds(landmarks, sources, sinks, *, n_cap: int, k: int,
              k_prime: int, layout: PlaneLayout = REPLICATED
              ) -> "PlaneStore":
        """Alg-1 seed planes: landmark lanes self-seeded, leaf masks hashed
        into BL buckets.  Every build/rebuild starts here; the delta rebuild
        resets invalidated entries back to exactly these values."""
        dl = dl_seed_plane(landmarks, n_cap=n_cap, k=k)
        return PlaneStore(dl, dl,
                          bl_seed_plane(sources, n_cap=n_cap,
                                        k_prime=k_prime),
                          bl_seed_plane(sinks, n_cap=n_cap, k_prime=k_prime),
                          landmarks, sources, sinks, layout=layout)

    def seed_frontiers(self) -> tuple[jax.Array, jax.Array]:
        """(frontier_fwd, frontier_bwd) — the vertices whose seed rows are
        non-empty per propagation direction (landmarks ∪ leaf mask)."""
        lm = jnp.zeros((self.n_cap,), jnp.bool_).at[self.landmarks].set(
            True, mode="drop")
        return lm | self.bl_sources, lm | self.bl_sinks

    # ---- fused planes ---------------------------------------------------
    def fused(self, *, reverse: bool = False) -> jax.Array:
        """(n_cap, k + k') fused plane per direction: DL lanes first, BL
        buckets after.  Lanes are independent under the OR monoid, so one
        fused fixpoint per direction computes the same bits as the four
        separate family fixpoints — in half the dispatches."""
        if reverse:
            return jnp.concatenate([self.dl_out, self.bl_out], axis=1)
        return jnp.concatenate([self.dl_in, self.bl_in], axis=1)

    def with_fused(self, x_fwd: jax.Array, x_bwd: jax.Array,
                   **meta) -> "PlaneStore":
        """Split fused direction planes back into the four family planes."""
        k = self.k
        return PlaneStore(x_fwd[:, :k], x_bwd[:, :k],
                          x_fwd[:, k:], x_bwd[:, k:],
                          meta.get("landmarks", self.landmarks),
                          meta.get("bl_sources", self.bl_sources),
                          meta.get("bl_sinks", self.bl_sinks),
                          layout=self.layout)

    # ---- delta rebuild's partial reset ----------------------------------
    def reset_invalid(self, seeds: "PlaneStore", dirty_fwd, dirty_bwd,
                      fresh_fwd, fresh_bwd) -> tuple[jax.Array, jax.Array]:
        """(x_fwd, x_bwd) — fused planes with every invalidated entry reset
        to its Alg-1 seed value: an entry is invalid iff its row is dirty
        (the vertex is in the deleted-edge invalidation closure for that
        direction) or its column is fresh (landmark / leaf-bucket churn).
        Row-parallel, so it keeps whatever row sharding the planes carry."""
        def reset(old, seed, dirty, fresh):
            return jnp.where(dirty[:, None] | fresh[None, :], seed, old)

        return (reset(self.fused(), seeds.fused(), dirty_fwd, fresh_fwd),
                reset(self.fused(reverse=True), seeds.fused(reverse=True),
                      dirty_bwd, fresh_bwd))

    # ---- packing / accounting -------------------------------------------
    def pack(self) -> Q.PackedLabels:
        return Q.pack_labels(self.dl_in, self.dl_out, self.bl_in,
                             self.bl_out)

    @staticmethod
    def pack_rows(plane: jax.Array) -> jax.Array:
        """Layout-aware bool->word packing: (rows, k) -> (rows, W) uint32.
        Every op touches only the lane axis (zero-extend, reshape, weighted
        sum), so the packing is row-parallel and preserves whatever row
        sharding the plane carries — a vertex-sharded plane packs
        shard-locally with no cross-device traffic.  The packed halo path
        relies on this: planes pack OUTSIDE the shard_map and the words
        inherit the rows' placement."""
        return bitset.pack(plane)

    @staticmethod
    def unpack_rows(words: jax.Array, k: int,
                    dtype=jnp.uint8) -> jax.Array:
        """Inverse of :meth:`pack_rows`; row-parallel and
        sharding-preserving for the same reason."""
        return bitset.unpack(words, k).astype(dtype)

    def label_bytes(self) -> int:
        """Logical (whole-index) bool-plane bytes across all four planes."""
        return sum(int(x.size) * x.dtype.itemsize
                   for x in (self.dl_in, self.dl_out, self.bl_in,
                             self.bl_out))


def dl_seed_plane(landmarks: jax.Array, *, n_cap: int, k: int) -> jax.Array:
    """(n_cap, k) uint8 — Alg-1 DL seeds: lane l self-seeded at landmark l."""
    seed = jnp.zeros((n_cap, k), jnp.uint8)
    return seed.at[landmarks, jnp.arange(k)].set(1, mode="drop")


def bl_seed_plane(mask: jax.Array, *, n_cap: int, k_prime: int) -> jax.Array:
    """(n_cap, k') uint8 — Alg-1 BL seeds: leaf ``mask`` hashed to buckets."""
    ids = jnp.arange(n_cap, dtype=jnp.int32)
    h = leaf_hash(ids, k_prime)
    onehot = jnp.arange(k_prime, dtype=jnp.int32)[None, :] == h[:, None]
    return (onehot & mask[:, None]).astype(jnp.uint8)


def per_device_label_bytes(obj) -> int:
    """Bytes of label-plane storage resident on ONE device — the quantity
    the vertex-sharded layout divides by the shard count.  ``obj`` is a
    PlaneStore, DBLIndex, or any pytree containing the four planes under
    the usual field names."""
    total = 0
    for name in ("dl_in", "dl_out", "bl_in", "bl_out"):
        arr = getattr(obj, name)
        shards = getattr(arr, "addressable_shards", None)
        if shards:
            total += int(shards[0].data.nbytes)
        else:
            total += int(arr.size) * arr.dtype.itemsize
    return total


# ----------------------------------------------------------- shard plan
class _DirPlan(NamedTuple):
    """One propagation direction's edge partition + halo routing.

    Edges are bucketed by the owner of their *receiving* endpoint (so the
    segment reduction is shard-local); the pushing endpoint resolves to a
    slot in the shard's combined table ``[local rows | halo buffer]``.
    ``h_send[s, t]`` lists the local row ids shard ``s`` must ship to shard
    ``t`` each round — exactly the vertices of ``s`` with a cut edge into
    ``t``'s rows, in the slot order ``t``'s edges expect.

    Each shard's bucket is sorted by ``e_recv`` (order is irrelevant to the
    bool path's segment_max but lets the packed path run its segmented-scan
    OR directly), with padding entries carrying the out-of-range sentinel
    ``e_recv == n_loc`` so both reductions drop them; ``e_start``/``e_tail``
    are the precomputed segment-boundary flags of that sorted order."""
    e_slot: jax.Array    # (d, E_pad) int32 — pushing endpoint's table slot
    e_recv: jax.Array    # (d, E_pad) int32 — receiving endpoint, local row
    e_gid: jax.Array     # (d, E_pad) int32 — global edge slot (live/cutoffs)
    e_valid: jax.Array   # (d, E_pad) bool  — padding mask
    h_send: jax.Array    # (d, d, H) int32  — local rows to send, per peer
    h_valid: jax.Array   # (d, d, H) bool
    e_start: jax.Array   # (d, E_pad) bool  — first entry of each recv segment
    e_tail: jax.Array    # (d, E_pad) bool  — last entry of each recv segment
    # --- sparse-halo hub lane (PR 10; None on hub-free plans) -----------
    # The top-`hub_count` highest-cut-degree vertices (frozen at plan
    # time) leave the per-pair compaction buckets during sparse rounds and
    # travel once per round on a broadcast psum lane instead of being
    # duplicated into up to d-1 pair buffers.
    h_hub: jax.Array | None = None   # (d, d, H) bool — h_send entry is a hub
    hubs: jax.Array | None = None    # (Hub,) int32 global ids, pad = n_cap
    hub_slot: jax.Array | None = None  # (d, Hub) int32 receiver-side slot
    #                                     into [local | halo]; pad slot is
    #                                     n_loc + d*H (scatter-dropped)
    host: tuple | None = None        # numpy mirrors for O(Δm) extension —
    #                                   never crosses into jit


class ShardPlan(NamedTuple):
    """Host-built routing tables for one (edge set, mesh) pair.

    Rebuilt whenever the edge set changes shape (insert batches append
    edges; compact renumbers slots) — tombstones do NOT invalidate it, the
    live mask is gathered per round via ``e_gid``.  Array extents are
    rounded up to granules so steady insert streams reuse the compiled
    fixpoint executables instead of recompiling per batch; the granules the
    plan was built with are recorded so :func:`extend_plan` (and the
    rebuild fallbacks) round on the SAME grid — extending a custom-granule
    plan on the default grid would spill to extents a from-scratch build
    never picks, churning compiled shapes for no reason."""
    mesh: Mesh
    n_cap: int
    m: int               # edge prefix the plan covers
    fwd: _DirPlan
    bwd: _DirPlan
    edge_granule: int = 1024
    halo_granule: int = 64
    hub_count: int = 0   # requested hub-lane width (0 = no hub lane)

    @property
    def shards(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def axis(self) -> str:
        return self.mesh.axis_names[0]


def _round_up(x: int, granule: int) -> int:
    return max(granule, -(-x // granule) * granule)


class _DirHost(NamedTuple):
    """Numpy mirrors of one direction's routing tables.  Kept on the plan
    (``_DirPlan.host``) so :func:`extend_plan` never round-trips the O(E)
    device arrays back to the host per batch — the D2H readback was the
    dominant cost of small-Δm extensions on small graphs (the BENCH_PR9
    Email regression).  ``e_start``/``e_tail`` are derived from ``e_recv``
    at upload time and are not mirrored."""
    e_slot: np.ndarray
    e_recv: np.ndarray
    e_gid: np.ndarray
    e_valid: np.ndarray
    h_send: np.ndarray
    h_valid: np.ndarray
    h_hub: np.ndarray | None
    hub_slot: np.ndarray | None
    hubs: np.ndarray | None      # REAL hub ids (unpadded, sorted ascending)


def _select_hubs(need: list, hub_count: int) -> np.ndarray:
    """Top-`hub_count` cut vertices by cut degree (= number of (receiver,
    sender) need lists containing the vertex).  Degree-1 vertices are
    excluded — a broadcast lane only pays off when a row would otherwise be
    duplicated into several pair buckets.  Deterministic: ties break on
    vertex id, result sorted ascending (membership via searchsorted)."""
    d = len(need)
    lists = [need[t][s] for t in range(d) for s in range(d)
             if need[t][s].size]
    if hub_count <= 0 or not lists:
        return np.zeros(0, np.int64)
    verts, cnts = np.unique(np.concatenate(lists), return_counts=True)
    keep = cnts >= 2
    verts, cnts = verts[keep], cnts[keep]
    order = np.lexsort((verts, -cnts))
    return np.sort(verts[order[:hub_count]])


def _build_dir(push: np.ndarray, recv: np.ndarray, m: int, n_loc: int,
               d: int, edge_granule: int, halo_granule: int,
               hub_count: int = 0) -> _DirPlan:
    gids = np.arange(m, dtype=np.int64)
    owner_recv = recv[:m].astype(np.int64) // n_loc
    owner_push = push[:m].astype(np.int64) // n_loc
    # bucket sorted by local receiving row: the packed path's segmented
    # scan needs non-decreasing segment ids, and the bool path's
    # segment_max is order-insensitive — one plan serves both
    per_shard = []
    for t in range(d):
        e = gids[owner_recv == t]
        per_shard.append(e[np.argsort(recv[e], kind="stable")])
    # halo need sets: need[t][s] = sorted unique push-vertices owned by s
    # that t's edge bucket references (s != t)
    need = [[np.zeros(0, np.int64)] * d for _ in range(d)]
    for t in range(d):
        e = per_shard[t]
        for s in range(d):
            if s == t:
                continue
            sel = e[owner_push[e] == s]
            need[t][s] = np.unique(push[sel])
    H = _round_up(max([1] + [need[t][s].size for t in range(d)
                             for s in range(d)]), halo_granule)
    E_pad = _round_up(max([1] + [e.size for e in per_shard]), edge_granule)

    e_slot = np.zeros((d, E_pad), np.int32)
    # padding entries carry the out-of-range recv sentinel: both the bool
    # segment_max and the packed tail scatter drop ids >= n_loc, and the
    # sentinel keeps each sorted row non-decreasing (pads sort last)
    e_recv = np.full((d, E_pad), n_loc, np.int32)
    e_gid = np.zeros((d, E_pad), np.int32)
    e_valid = np.zeros((d, E_pad), bool)
    h_send = np.zeros((d, d, H), np.int32)
    h_valid = np.zeros((d, d, H), bool)
    e_start = np.zeros((d, E_pad), bool)
    e_tail = np.zeros((d, E_pad), bool)
    for t in range(d):
        e = per_shard[t]
        ne = e.size
        e_gid[t, :ne] = e
        e_valid[t, :ne] = True
        e_recv[t, :ne] = recv[e] - t * n_loc
        pu = push[e]
        own = owner_push[e]
        slot = np.where(own == t, pu - t * n_loc, 0).astype(np.int64)
        for s in range(d):
            if s == t or need[t][s].size == 0:
                continue
            sel = own == s
            pos = np.searchsorted(need[t][s], pu[sel])
            slot[sel] = n_loc + s * H + pos
        e_slot[t, :ne] = slot
    for s in range(d):
        for t in range(d):
            ids = need[t][s]
            h_send[s, t, :ids.size] = ids - s * n_loc
            h_valid[s, t, :ids.size] = True
    e_start[:, 0] = True
    e_start[:, 1:] = e_recv[:, 1:] != e_recv[:, :-1]
    e_tail[:, :-1] = e_recv[:, 1:] != e_recv[:, :-1]
    e_tail[:, -1] = True
    # ---- hub lane: frozen at plan time ---------------------------------
    h_hub = hub_slot = hubs_arr = hubs_np = None
    if hub_count > 0:
        hubs_np = _select_hubs(need, hub_count)
        h_hub = np.zeros((d, d, H), bool)
        hubs_arr = np.full(hub_count, n_loc * d, np.int64)
        hubs_arr[:hubs_np.size] = hubs_np
        # receiver-side slot of hub j in [local rows | halo buffer]; the
        # pad sentinel n_loc + d*H is one past the combined table, so the
        # scatter drops it
        hub_slot = np.full((d, hub_count), n_loc + d * H, np.int64)
        if hubs_np.size:
            for t in range(d):
                for s in range(d):
                    ids = need[t][s]
                    if ids.size == 0:
                        continue
                    j = np.searchsorted(hubs_np, ids)
                    jc = np.minimum(j, hubs_np.size - 1)
                    ishub = (j < hubs_np.size) & (hubs_np[jc] == ids)
                    h_hub[s, t, :ids.size] = ishub
                    pos = np.arange(ids.size)
                    hub_slot[t, j[ishub]] = n_loc + s * H + pos[ishub]
    return _DirPlan(
        jnp.asarray(e_slot), jnp.asarray(e_recv),
        jnp.asarray(e_gid), jnp.asarray(e_valid),
        jnp.asarray(h_send), jnp.asarray(h_valid),
        jnp.asarray(e_start), jnp.asarray(e_tail),
        h_hub=None if h_hub is None else jnp.asarray(h_hub),
        hubs=None if hubs_arr is None else jnp.asarray(
            hubs_arr.astype(np.int32)),
        hub_slot=None if hub_slot is None else jnp.asarray(
            hub_slot.astype(np.int32)),
        host=_DirHost(e_slot, e_recv, e_gid, e_valid,
                      h_send, h_valid, h_hub, hub_slot, hubs_np))


def shard_plan(src, dst, m: int, n_cap: int, mesh: Mesh, *,
               edge_granule: int = 1024,
               halo_granule: int = 64,
               hub_count: int = 0) -> ShardPlan:
    """Partition the edge prefix ``[0, m)`` for a vertex mesh (host-side).

    ``src``/``dst`` are the graph's (m_cap,) edge arrays (numpy or device;
    synced once).  O(m log m) numpy work — paid at bind time and after
    mutations that extend or renumber the edge arrays, never per query.
    ``hub_count > 0`` additionally selects the top-`hub_count` cut-degree
    vertices per direction for the sparse halo's broadcast lane (frozen
    until the next from-scratch plan)."""
    layout = vertex_layout(mesh)
    n_loc = _check_rows(n_cap, layout)
    src = np.asarray(src)
    dst = np.asarray(dst)
    d = layout.shards
    return ShardPlan(
        mesh, n_cap, int(m),
        fwd=_build_dir(src, dst, int(m), n_loc, d, edge_granule,
                       halo_granule, hub_count),
        bwd=_build_dir(dst, src, int(m), n_loc, d, edge_granule,
                       halo_granule, hub_count),
        edge_granule=edge_granule, halo_granule=halo_granule,
        hub_count=hub_count)


# ------------------------------------------- incremental plan extension
def _normalize_batch(new_src, new_dst, m0: int, dedupe: bool = True
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Normalize one insert batch for plan extension.  With ``dedupe``
    (the single-batch default) self-loops and in-batch duplicate pairs are
    dropped, keeping each pair's FIRST (lowest-gid) occurrence.

    Self-loops are OR/MIN no-ops in every fixpoint (a row relaxed into
    itself) and BFS no-ops (the pushing vertex is already visited), so the
    routing tables can skip them outright.  In-batch duplicates would
    double-count the same (push, recv) pair in a cut-edge bucket and its
    halo send list; keeping the first slot is sound because duplicate slots
    of one batch are created live together, ``graph.delete_edges`` kills
    every live duplicate of a pair at once, and the engine's per-lane
    ``m_at_submit`` cutoffs only ever land at batch boundaries — no cutoff
    can separate two slots of the same batch.  (The graph itself still
    appends every raw slot; only the routing tables dedupe.)

    ``dedupe=False`` keeps EVERY raw slot, exactly like a from-scratch
    ``_build_dir``.  That is the only sound mode for a window that spans
    multiple batches (the rebuild catch-up): a pair inserted, tombstoned,
    and re-inserted inside the window has a dead slot with a lower gid than
    its live twin, and the first-occurrence rule would route the dead slot
    (masked out per round via ``e_gid``) while dropping the live one.

    Returns (src, dst, gid, raw) with ``gid`` the kept edges' global slots
    (``m0 + position in the raw batch``) and ``raw`` the raw batch size."""
    src = np.asarray(new_src, np.int64).ravel()
    dst = np.asarray(new_dst, np.int64).ravel()
    raw = int(src.size)
    gid = m0 + np.arange(raw, dtype=np.int64)
    if raw == 0 or not dedupe:
        return src, dst, gid, raw
    hi = int(max(src.max(), dst.max())) + 1
    _, first = np.unique(src * hi + dst, return_index=True)
    keep = np.zeros(raw, bool)
    keep[first] = True
    keep &= src != dst
    return src[keep], dst[keep], gid[keep], raw


def _extend_dir(dp: _DirPlan, push: np.ndarray, recv: np.ndarray,
                gid: np.ndarray, n_loc: int, d: int, edge_granule: int,
                halo_granule: int) -> _DirPlan:
    """Merge a normalized Δ-batch into one direction's routing tables.

    The buckets must stay sorted by local receiving row with exactly one
    ``e_tail`` flag per segment — the packed fixpoint's
    ``bitset.segment_or_flags`` tail scatter uses ``.set`` and would lose
    OR bits if a recv id had runs in both the old and an appended region.
    So new edges are MERGED into recv-sorted position via two searchsorted
    passes (new gids sort after old gids within equal recv, reproducing
    exactly the from-scratch stable order of ``e_recv``/``e_gid``) —
    O(Δm log Δm) sort work plus O(E) memcpy, never a re-sort of the
    existing edges.

    Scope of the bit-for-bit claim: ``e_recv``/``e_gid``/``e_valid`` (and
    the derived ``e_start``/``e_tail``) match a from-scratch build exactly.
    ``h_send`` appends fresh cut vertices AFTER the existing slots —
    existing slot positions are the invariant compiled executables depend
    on — so when a fresh vertex sorts below an existing one the halo list
    order (and with it the ``e_slot`` values that index into it) diverges
    from the from-scratch globally-sorted order.  Only semantic equivalence
    holds there: the decoded (slot -> global pushing vertex) map is
    identical, which is what the fixpoint reads.

    Cost model (the BENCH_PR9 Email fix): the tables are read from the
    plan's numpy mirrors (``_DirHost``), never synced back from the device
    — the per-batch D2H readback of six O(E) arrays used to dominate the
    bare-op cost on small graphs.  Per bucket, when the batch appends in
    recv-sorted position (its smallest local recv row is >= the bucket's
    last occupied one — trivially true for untouched buckets) the two-pass
    searchsorted merge is skipped outright: the old prefix is one
    contiguous memcpy and the Δ entries land in the granule-headroom tail,
    which is exactly the position the full merge would pick."""
    host = dp.host
    if host is None:
        hub_ids = None if dp.hubs is None else np.asarray(dp.hubs)
        host = _DirHost(
            np.asarray(dp.e_slot).astype(np.int64), np.asarray(dp.e_recv),
            np.asarray(dp.e_gid), np.asarray(dp.e_valid),
            np.asarray(dp.h_send), np.asarray(dp.h_valid),
            None if dp.h_hub is None else np.asarray(dp.h_hub),
            None if dp.hub_slot is None else
            np.asarray(dp.hub_slot).astype(np.int64),
            None if hub_ids is None else
            hub_ids[hub_ids < n_loc * d].astype(np.int64))
    e_slot = host.e_slot
    e_recv = host.e_recv
    e_gid = host.e_gid
    e_valid = host.e_valid
    h_send = host.h_send
    h_valid = host.h_valid
    h_hub = host.h_hub
    hub_slot = host.hub_slot
    hubs_np = host.hubs
    E_old = e_recv.shape[1]
    H_old = h_send.shape[2]
    ne = e_valid.sum(axis=1)                       # (d,) valid prefix sizes
    hc = h_valid.sum(axis=2)                       # (d, d) halo list sizes
    owner_recv = recv // n_loc
    owner_push = push // n_loc
    cut = owner_push != owner_recv

    # ---- halo send lists: append fresh cut vertices per (sender, receiver)
    # pair.  Existing vertices keep their slot positions (the routing-table
    # invariant every already-compiled executable depends on); fresh ones
    # take the next positions in the pair's list.
    slot_pos: dict[tuple[int, int], tuple[np.ndarray, np.ndarray]] = {}
    new_halo: dict[tuple[int, int], np.ndarray] = {}
    H_needed = H_old
    if cut.any():
        pairs = {(int(s), int(t))
                 for s, t in zip(owner_push[cut], owner_recv[cut])}
        for s, t in sorted(pairs):
            sel = cut & (owner_push == s) & (owner_recv == t)
            verts = np.unique(push[sel])
            c = int(hc[s, t])
            need = h_send[s, t, :c].astype(np.int64) + s * n_loc
            order = np.argsort(need, kind="stable")
            sorted_need = need[order]
            pos = np.empty(verts.size, np.int64)
            if c:
                j = np.searchsorted(sorted_need, verts)
                jc = np.minimum(j, c - 1)
                found = (j < c) & (sorted_need[jc] == verts)
                pos[found] = order[jc[found]]
            else:
                found = np.zeros(verts.size, bool)
            fresh = verts[~found]
            pos[~found] = c + np.arange(fresh.size)
            slot_pos[(s, t)] = (verts, pos)
            new_halo[(s, t)] = fresh
            H_needed = max(H_needed, c + fresh.size)
    grew_h = H_needed > H_old
    H_new = _round_up(H_needed, halo_granule) if grew_h else H_old
    hh2 = hub_slot2 = None
    if grew_h:
        hs2 = np.zeros((d, d, H_new), np.int32)
        hv2 = np.zeros((d, d, H_new), bool)
        hs2[:, :, :H_old] = h_send
        hv2[:, :, :H_old] = h_valid
        if h_hub is not None:
            hh2 = np.zeros((d, d, H_new), bool)
            hh2[:, :, :H_old] = h_hub
        if hub_slot is not None:
            # the combined-table stride n_loc + s*H + pos changed: remap
            # the hub fill slots and move the drop sentinel to the new
            # table size, mirroring the e_slot remap below
            off = hub_slot - n_loc
            hub_slot2 = np.where(
                hub_slot >= n_loc + d * H_old, n_loc + d * H_new,
                np.where(hub_slot >= n_loc,
                         n_loc + (off // H_old) * H_new + off % H_old,
                         hub_slot))
    elif new_halo:
        hs2 = h_send.copy()
        hv2 = h_valid.copy()
        if h_hub is not None:
            hh2 = h_hub.copy()
        if hub_slot is not None:
            hub_slot2 = hub_slot.copy()
    else:
        hs2 = hv2 = None     # zero-cut early-out: reuse dp's device arrays
    for (s, t), fresh in new_halo.items():
        c = int(hc[s, t])
        hs2[s, t, c:c + fresh.size] = (fresh - s * n_loc).astype(np.int32)
        hv2[s, t, c:c + fresh.size] = True
        # fresh cut vertices that belong to the frozen hub set get their
        # hub flags + receiver fill slots as they enter the send lists
        if hubs_np is not None and hubs_np.size and fresh.size:
            j = np.searchsorted(hubs_np, fresh)
            jc = np.minimum(j, hubs_np.size - 1)
            ishub = (j < hubs_np.size) & (hubs_np[jc] == fresh)
            if ishub.any():
                pos = c + np.arange(fresh.size)
                hh2[s, t, pos[ishub]] = True
                hub_slot2[t, j[ishub]] = n_loc + s * H_new + pos[ishub]

    # ---- edge buckets: merge per receiving shard -----------------------
    counts = np.bincount(owner_recv, minlength=d)[:d]
    E_needed = int((ne + counts).max())
    E_new = _round_up(E_needed, edge_granule) if E_needed > E_old else E_old
    if grew_h:
        # the combined-table stride n_loc + s*H + pos changed: remap every
        # existing non-local slot into the new stride (vectorized O(E))
        off = e_slot - n_loc
        e_slot = np.where(e_slot >= n_loc,
                          n_loc + (off // H_old) * H_new + off % H_old,
                          e_slot)
    s2 = np.zeros((d, E_new), np.int32)
    r2 = np.full((d, E_new), n_loc, np.int32)
    g2 = np.zeros((d, E_new), np.int32)
    v2 = np.zeros((d, E_new), bool)
    for t in range(d):
        nold = int(ne[t])
        sel = owner_recv == t
        b = int(sel.sum())
        if b == 0:
            s2[t, :nold] = e_slot[t, :nold]
            r2[t, :nold] = e_recv[t, :nold]
            g2[t, :nold] = e_gid[t, :nold]
            v2[t, :nold] = True
            continue
        rl = recv[sel] - t * n_loc
        order = np.argsort(rl, kind="stable")
        rl_s = rl[order]
        gid_s = gid[sel][order]
        push_s = push[sel][order]
        own_s = owner_push[sel][order]
        slot_new = np.where(own_s == t, push_s - t * n_loc, 0)
        for s in np.unique(own_s[own_s != t]):
            verts, pos = slot_pos[(int(s), t)]
            msel = own_s == s
            k = np.searchsorted(verts, push_s[msel])
            slot_new[msel] = n_loc + int(s) * H_new + pos[k]
        if nold == 0 or rl_s[0] >= int(e_recv[t, nold - 1]):
            # append-sorted fast path: the whole batch lands at or after
            # the bucket's last occupied recv row, so the granule-headroom
            # tail positions are exactly the ones the two-pass merge would
            # pick (equal recv ids order new gids after old) — skip it
            s2[t, :nold] = e_slot[t, :nold]
            s2[t, nold:nold + b] = slot_new
            r2[t, :nold] = e_recv[t, :nold]
            r2[t, nold:nold + b] = rl_s
            g2[t, :nold] = e_gid[t, :nold]
            g2[t, nold:nold + b] = gid_s
            v2[t, :nold + b] = True
            continue
        old_r = e_recv[t, :nold].astype(np.int64)
        dst_old = np.arange(nold) + np.searchsorted(rl_s, old_r, "left")
        dst_new = np.searchsorted(old_r, rl_s, "right") + np.arange(b)
        s2[t, dst_old] = e_slot[t, :nold].astype(np.int32)
        s2[t, dst_new] = slot_new.astype(np.int32)
        r2[t, dst_old] = e_recv[t, :nold]
        r2[t, dst_new] = rl_s.astype(np.int32)
        g2[t, dst_old] = e_gid[t, :nold]
        g2[t, dst_new] = gid_s.astype(np.int32)
        v2[t, :nold + b] = True
    start = np.zeros((d, E_new), bool)
    tail = np.zeros((d, E_new), bool)
    start[:, 0] = True
    start[:, 1:] = r2[:, 1:] != r2[:, :-1]
    tail[:, :-1] = r2[:, 1:] != r2[:, :-1]
    tail[:, -1] = True
    # Defer the upload: return the numpy tables plus a finisher so
    # extend_plan can push BOTH directions' tables in one batched
    # device_put — per-array uploads (and the earlier stack-then-slice
    # variant, whose device-side slices cost a dispatch each) dominate
    # the bare-op cost on small graphs, and even one device_put per
    # direction is a visible slice of the Email bare op
    parts = [s2, r2, g2, v2, start, tail]
    if hs2 is not None:
        parts += [hs2, hv2]
        if hh2 is not None:
            parts.append(hh2)
        if hub_slot2 is not None:
            parts.append(hub_slot2.astype(np.int32))

    def finish(dev):
        s2j, r2j, g2j, v2j, startj, tailj = dev[:6]
        pos = 6
        if hs2 is not None:
            hs2j, hv2j = dev[pos:pos + 2]
            pos += 2
        else:
            hs2j, hv2j = dp.h_send, dp.h_valid
        hh2j = dp.h_hub
        if hs2 is not None and hh2 is not None:
            hh2j = dev[pos]
            pos += 1
        hub_slot2j = dp.hub_slot
        if hs2 is not None and hub_slot2 is not None:
            hub_slot2j = dev[pos]
        return _DirPlan(
            s2j, r2j, g2j, v2j, hs2j, hv2j, startj, tailj,
            h_hub=hh2j,
            hubs=dp.hubs,
            hub_slot=hub_slot2j,
            host=_DirHost(s2, r2, g2, v2,
                          h_send if hs2 is None else hs2,
                          h_valid if hv2 is None else hv2,
                          h_hub if hh2 is None else hh2,
                          hub_slot if hub_slot2 is None else hub_slot2,
                          hubs_np))

    return parts, finish


def extend_plan(plan: ShardPlan, new_src, new_dst, *,
                edge_granule: int | None = None,
                halo_granule: int | None = None,
                dedupe: bool = True) -> ShardPlan:
    """Append a Δ-batch of edges into an existing plan's routing tables —
    the O(m + Δm log Δm) incremental twin of :func:`shard_plan` (no re-sort
    of the m existing edges; the only per-edge work on them is memcpy).

    The new edges take global slots ``[plan.m, plan.m + Δ)`` — exactly what
    ``graph.insert_edges`` assigns — so the extended plan covers the same
    edge prefix a from-scratch ``shard_plan`` over the appended arrays
    would.  The equivalence contract: ``e_recv``/``e_gid``/``e_valid`` come
    out bit-identical to the from-scratch build (absent in-batch
    duplicates/self-loops, which ``dedupe`` drops from the tables);
    ``h_send``/``e_slot`` are only semantically equivalent — fresh halo
    vertices append after the existing slots instead of re-sorting the
    lists, so their order can diverge (see :func:`_extend_dir`).

    ``dedupe`` MUST be False when the batch spans more than one insert
    batch — e.g. the rebuild catch-up window — because a pair deleted and
    re-inserted across batches would have its live slot dropped in favor
    of its tombstoned twin (see :func:`_normalize_batch`).  With
    ``dedupe=False`` every raw slot enters the tables, exactly as in
    ``_build_dir`` (duplicates/self-loops are harmless in the buckets),
    and the bucket arrays are bit-identical to from-scratch even on
    hostile input.

    Shape discipline: the padded extents ``E_pad``/``H`` are KEPT as long
    as the appended entries fit the granule-rounded tails, so compiled
    fixpoint executables keyed on those extents keep firing across steady
    insert streams; a bucket overflow spills to ``_round_up(needed,
    granule)`` — the same extent a from-scratch build would pick.
    Granules default to the ones ``plan`` was built with (recorded on the
    plan), so extension rounds on the same grid as the original build.  A
    batch that adds no cut edge leaves ``h_send``/``h_valid`` untouched
    (the very arrays, not copies), and a batch that normalizes to nothing
    returns the plan with only ``m`` advanced."""
    edge_granule = plan.edge_granule if edge_granule is None else edge_granule
    halo_granule = plan.halo_granule if halo_granule is None else halo_granule
    layout = vertex_layout(plan.mesh)
    n_loc = _check_rows(plan.n_cap, layout)
    d = layout.shards
    src, dst, gid, raw = _normalize_batch(new_src, new_dst, plan.m, dedupe)
    m2 = plan.m + raw
    if src.size == 0:
        return plan._replace(m=m2)
    fparts, ffin = _extend_dir(plan.fwd, src, dst, gid, n_loc, d,
                               edge_granule, halo_granule)
    bparts, bfin = _extend_dir(plan.bwd, dst, src, gid, n_loc, d,
                               edge_granule, halo_granule)
    # one batched device_put covering BOTH directions' updated tables —
    # upload dispatch, not bandwidth, is the bare-op floor on small graphs
    dev = list(jax.device_put(tuple(fparts + bparts)))
    return ShardPlan(
        plan.mesh, plan.n_cap, m2,
        fwd=ffin(dev[:len(fparts)]),
        bwd=bfin(dev[len(fparts):]),
        edge_granule=edge_granule, halo_granule=halo_granule,
        hub_count=plan.hub_count)


# ------------------------------------------------- sharded collectives
def _vspecs(mesh: Mesh):
    ax = mesh.axis_names[0]
    return ax, P(ax, None), P(ax), P()


@functools.partial(jax.jit, static_argnames=("mesh", "max_iters"))
def _halo_propagate_impl(x, frontier, live, e_slot, e_recv, e_gid, e_valid,
                         h_send, h_valid, *, mesh: Mesh, max_iters: int):
    ax, plane_sp, vec_sp, rep = _vspecs(mesh)
    d = int(mesh.devices.size)
    n_cap, kf = x.shape
    n_loc = n_cap // d
    H = h_send.shape[2]

    def shard_body(x, fr, live, e_slot, e_recv, e_gid, e_valid, hs, hv):
        e_slot, e_recv, e_gid, e_valid = (a[0] for a in
                                          (e_slot, e_recv, e_gid, e_valid))
        hs, hv = hs[0], hv[0]

        def body(state):
            x, fr, it = state
            # halo exchange: boundary frontier rows only — non-frontier
            # boundary rows travel as zeros (no-ops under OR), and
            # interior rows never travel at all
            sf = hv & fr[hs]                               # (d, H)
            sr = jnp.where(sf[..., None], x[hs], 0)        # (d, H, kf)
            rf = jax.lax.all_to_all(sf, ax, 0, 0)
            rr = jax.lax.all_to_all(sr, ax, 0, 0)
            comb = jnp.concatenate([x, rr.reshape(d * H, kf)], axis=0)
            frc = jnp.concatenate([fr, rf.reshape(d * H)], axis=0)
            active = frc[e_slot] & live[e_gid] & e_valid
            contrib = comb[e_slot] * active[:, None].astype(x.dtype)
            agg = jax.ops.segment_max(contrib, e_recv, num_segments=n_loc)
            new = jnp.maximum(x, agg)
            return new, jnp.any(new != x, axis=-1), it + 1

        def cond(state):
            _, fr, it = state
            alive = jax.lax.psum(fr.sum().astype(jnp.int32), ax) > 0
            return alive & (it < max_iters)

        x, fr, it = jax.lax.while_loop(cond, body,
                                       (x, fr.astype(jnp.bool_),
                                        jnp.int32(0)))
        trunc = jax.lax.psum(fr.sum().astype(jnp.int32), ax) > 0
        iters = jnp.where(trunc, jnp.int32(max_iters + 1), it)
        return x, iters

    sm = shard_map(
        shard_body, mesh=mesh, check_vma=False,
        in_specs=(plane_sp, vec_sp, rep,
                  plane_sp, plane_sp, plane_sp, plane_sp,
                  P(ax, None, None), P(ax, None, None)),
        out_specs=(plane_sp, rep))
    return sm(x, frontier, live, e_slot, e_recv, e_gid, e_valid,
              h_send, h_valid)


@functools.partial(jax.jit, static_argnames=("mesh", "max_iters"))
def _halo_propagate_min_impl(x, frontier, live, e_slot, e_recv, e_gid,
                             e_valid, h_send, h_valid, *, mesh: Mesh,
                             max_iters: int):
    """MIN-monoid twin of ``_halo_propagate_impl`` for int32 rank planes
    (the "il" plug-in family).  Same round structure and frontier
    evolution; the identity element flips from 0 to int32 max — inactive
    contributions travel as ``_INT_MAX`` so ``segment_min`` drops them,
    exactly as in ``propagate._step_min``."""
    ax, plane_sp, vec_sp, rep = _vspecs(mesh)
    d = int(mesh.devices.size)
    n_cap, kf = x.shape
    n_loc = n_cap // d
    H = h_send.shape[2]

    def shard_body(x, fr, live, e_slot, e_recv, e_gid, e_valid, hs, hv):
        e_slot, e_recv, e_gid, e_valid = (a[0] for a in
                                          (e_slot, e_recv, e_gid, e_valid))
        hs, hv = hs[0], hv[0]

        def body(state):
            x, fr, it = state
            # boundary frontier rows only; non-frontier boundary rows
            # travel as int32 max (no-ops under MIN)
            sf = hv & fr[hs]                               # (d, H)
            sr = jnp.where(sf[..., None], x[hs], _INT_MAX)
            rf = jax.lax.all_to_all(sf, ax, 0, 0)
            rr = jax.lax.all_to_all(sr, ax, 0, 0)
            comb = jnp.concatenate([x, rr.reshape(d * H, kf)], axis=0)
            frc = jnp.concatenate([fr, rf.reshape(d * H)], axis=0)
            active = frc[e_slot] & live[e_gid] & e_valid
            contrib = jnp.where(active[:, None], comb[e_slot], _INT_MAX)
            agg = jax.ops.segment_min(contrib, e_recv, num_segments=n_loc)
            new = jnp.minimum(x, agg)
            return new, jnp.any(new != x, axis=-1), it + 1

        def cond(state):
            _, fr, it = state
            alive = jax.lax.psum(fr.sum().astype(jnp.int32), ax) > 0
            return alive & (it < max_iters)

        x, fr, it = jax.lax.while_loop(cond, body,
                                       (x, fr.astype(jnp.bool_),
                                        jnp.int32(0)))
        trunc = jax.lax.psum(fr.sum().astype(jnp.int32), ax) > 0
        iters = jnp.where(trunc, jnp.int32(max_iters + 1), it)
        return x, iters

    sm = shard_map(
        shard_body, mesh=mesh, check_vma=False,
        in_specs=(plane_sp, vec_sp, rep,
                  plane_sp, plane_sp, plane_sp, plane_sp,
                  P(ax, None, None), P(ax, None, None)),
        out_specs=(plane_sp, rep))
    return sm(x, frontier, live, e_slot, e_recv, e_gid, e_valid,
              h_send, h_valid)


@functools.partial(jax.jit, static_argnames=("mesh", "max_iters", "k"))
def _halo_propagate_packed_impl(xw, frontier, live, e_slot, e_recv, e_gid,
                                e_valid, e_start, e_tail, h_send, h_valid,
                                *, mesh: Mesh, max_iters: int, k: int):
    """Word-plane twin of ``_halo_propagate_impl``: same round structure,
    but the shard-local state and the exchanged halo rows are (rows, W)
    uint32 words — per-round boundary traffic shrinks 32x.  The plan's
    recv-sorted buckets + precomputed segment flags feed
    ``bitset.segment_or_flags`` directly (no per-round sort)."""
    ax, plane_sp, vec_sp, rep = _vspecs(mesh)
    d = int(mesh.devices.size)
    n_cap, W = xw.shape
    n_loc = n_cap // d
    H = h_send.shape[2]

    def shard_body(xw, fr, live, e_slot, e_recv, e_gid, e_valid, e_start,
                   e_tail, hs, hv):
        e_slot, e_recv, e_gid, e_valid, e_start, e_tail = (
            a[0] for a in (e_slot, e_recv, e_gid, e_valid, e_start, e_tail))
        hs, hv = hs[0], hv[0]
        mask = bitset.pad_mask(k)

        def body(state):
            xw, fr, it = state
            sf = hv & fr[hs]                               # (d, H)
            sr = jnp.where(sf[..., None], xw[hs], jnp.uint32(0))
            rf = jax.lax.all_to_all(sf, ax, 0, 0)
            rr = jax.lax.all_to_all(sr, ax, 0, 0)
            comb = jnp.concatenate([xw, rr.reshape(d * H, W)], axis=0)
            frc = jnp.concatenate([fr, rf.reshape(d * H)], axis=0)
            active = frc[e_slot] & live[e_gid] & e_valid
            vals = jnp.where(active[:, None], comb[e_slot], jnp.uint32(0))
            agg = bitset.segment_or_flags(vals, e_start, e_tail, e_recv,
                                          n_loc)
            new = (xw | agg) & mask
            return new, jnp.any(new != xw, axis=-1), it + 1

        def cond(state):
            _, fr, it = state
            alive = jax.lax.psum(fr.sum().astype(jnp.int32), ax) > 0
            return alive & (it < max_iters)

        xw, fr, it = jax.lax.while_loop(cond, body,
                                        (xw, fr.astype(jnp.bool_),
                                         jnp.int32(0)))
        trunc = jax.lax.psum(fr.sum().astype(jnp.int32), ax) > 0
        iters = jnp.where(trunc, jnp.int32(max_iters + 1), it)
        return xw, iters

    sm = shard_map(
        shard_body, mesh=mesh, check_vma=False,
        in_specs=(plane_sp, vec_sp, rep,
                  plane_sp, plane_sp, plane_sp, plane_sp, plane_sp,
                  plane_sp, P(ax, None, None), P(ax, None, None)),
        out_specs=(plane_sp, rep))
    return sm(xw, frontier, live, e_slot, e_recv, e_gid, e_valid, e_start,
              e_tail, h_send, h_valid)


def halo_propagate(plan: ShardPlan, x: jax.Array, frontier: jax.Array,
                   live: jax.Array, *, reverse: bool = False,
                   max_iters: int = 256, monoid: str = "or",
                   plane_repr: str = "bool", halo_mode: str = "dense",
                   telemetry=None, halo_caps: tuple[int, ...] | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Vertex-sharded twin of ``propagate.propagate``.

    Same contract: returns (labels, iters) with ``iters = max_iters + 1``
    when the loop was cut off with the (global) frontier still non-empty.
    Bitwise-identical to the replicated fixpoint: each round performs the
    same edge-parallel relaxation, just with the rows partitioned and the
    boundary frontier rows exchanged via one ``all_to_all``.

    ``plane_repr="packed"`` runs the word-plane fixpoint: the bool plane is
    packed shard-locally (``PlaneStore.pack_rows`` is row-parallel, so the
    words inherit the rows' sharding), halo rows cross the mesh as uint32
    words (32x less boundary traffic), and the result unpacks back to the
    caller's dtype — bitwise equal to the bool path.

    ``monoid="min"`` relaxes int32 rank planes (the "il" plug-in family)
    with ``_halo_propagate_min_impl``; like the replicated engine it has
    no packed form (min planes are ranks, not bit lanes).

    ``halo_mode="sparse"`` runs the compacted changed-row exchange
    (``core.halo``): per-round, only the boundary rows whose value changed
    travel, in power-of-two capacity buckets with a dense fallback on
    overflow, hub rows ride a broadcast psum lane, and all-quiet pairs
    skip their payload entirely — bitwise equal to the dense oracle in
    every repr/monoid combination.  ``telemetry`` (a
    ``core.halo.HaloTelemetry``) accumulates modeled halo bytes and round
    counts for either mode; ``halo_caps`` overrides the sparse capacity
    schedule (``halo.bucket_caps``)."""
    check_plane_repr(plane_repr)
    check_halo_mode(halo_mode)
    if monoid not in ("or", "min"):
        raise ValueError(f"unknown monoid {monoid!r}")
    if halo_mode == "sparse":
        from . import halo as _halo
        return _halo.sparse_halo_propagate(
            plan, x, frontier, live, reverse=reverse, max_iters=max_iters,
            monoid=monoid, plane_repr=plane_repr, telemetry=telemetry,
            caps=halo_caps)
    dp = plan.bwd if reverse else plan.fwd
    d = int(plan.mesh.devices.size)
    H = dp.h_send.shape[2]

    def _note(iters, row_bytes):
        if telemetry is not None:
            # dense byte model: every ordered pair ships its full H-row
            # halo buffer (rows + send flags) every round
            telemetry.add_dense(iters, d * (d - 1) * H * (row_bytes + 1),
                                max_iters)

    if monoid == "min":
        if plane_repr == "packed":
            raise ValueError(
                "plane_repr='packed' supports the OR monoid only")
        out, iters = _halo_propagate_min_impl(
            x, frontier, live, dp.e_slot, dp.e_recv, dp.e_gid, dp.e_valid,
            dp.h_send, dp.h_valid, mesh=plan.mesh, max_iters=max_iters)
        _note(iters, 4 * x.shape[1])
        return out, iters
    if plane_repr == "packed":
        k = x.shape[1]
        xw = PlaneStore.pack_rows(x)
        out_w, iters = _halo_propagate_packed_impl(
            xw, frontier, live, dp.e_slot, dp.e_recv, dp.e_gid, dp.e_valid,
            dp.e_start, dp.e_tail, dp.h_send, dp.h_valid,
            mesh=plan.mesh, max_iters=max_iters, k=k)
        _note(iters, 4 * bitset.n_words(k))
        return PlaneStore.unpack_rows(out_w, k, x.dtype), iters
    out, iters = _halo_propagate_impl(
        x, frontier, live, dp.e_slot, dp.e_recv, dp.e_gid, dp.e_valid,
        dp.h_send, dp.h_valid, mesh=plan.mesh, max_iters=max_iters)
    _note(iters, x.shape[1])
    return out, iters


@functools.partial(jax.jit, static_argnames=("mesh",))
def sharded_seed_scatter(x: jax.Array, at_src: jax.Array, at_dst: jax.Array,
                         *, mesh: Mesh) -> tuple[jax.Array, jax.Array]:
    """Sharded twin of ``propagate.seed_scatter_or`` specialised to the
    Alg-3 insert seeding pattern: OR row ``x[at_src[i]]`` into row
    ``x[at_dst[i]]``.  The b gathered source rows cross shards once via a
    ``psum`` of per-shard masked gathers (O(b·k), no plane movement); the
    scatter-OR lands only on locally-owned rows.  Returns (seeded planes,
    changed-row frontier), both row-sharded."""
    ax, plane_sp, vec_sp, rep = _vspecs(mesh)
    d = int(mesh.devices.size)
    n_loc = x.shape[0] // d

    def shard_body(x, ns, nd):
        lo = jax.lax.axis_index(ax).astype(jnp.int32) * n_loc
        src_local = (ns >= lo) & (ns < lo + n_loc)
        rows = jnp.where(src_local[:, None],
                         x[jnp.clip(ns - lo, 0, n_loc - 1)], 0)
        rows = jax.lax.psum(rows, ax)
        owned = (nd >= lo) & (nd < lo + n_loc)
        ldst = jnp.where(owned, nd - lo, n_loc)   # n_loc => dropped
        new = x.at[ldst].max(rows.astype(x.dtype), mode="drop")
        return new, jnp.any(new != x, axis=-1)

    sm = shard_map(shard_body, mesh=mesh, check_vma=False,
                   in_specs=(plane_sp, rep, rep),
                   out_specs=(plane_sp, vec_sp))
    return sm(x, jnp.asarray(at_src, jnp.int32),
              jnp.asarray(at_dst, jnp.int32))


@functools.partial(jax.jit, static_argnames=("mesh",))
def sharded_seed_scatter_min(x: jax.Array, at_src: jax.Array,
                             at_dst: jax.Array, *, mesh: Mesh
                             ) -> tuple[jax.Array, jax.Array]:
    """MIN twin of ``sharded_seed_scatter`` for int32 rank planes: take
    ``min(x[at_dst[i]], x[at_src[i]])`` row-wise.  The psum row gather is
    exact for any-sign int32 because each source row has exactly one owner
    shard (everyone else contributes zeros); rows whose *destination* is
    out of range (padding) are dropped by the scatter, so the zero-filled
    rows of out-of-range sources never land anywhere."""
    ax, plane_sp, vec_sp, rep = _vspecs(mesh)
    d = int(mesh.devices.size)
    n_loc = x.shape[0] // d

    def shard_body(x, ns, nd):
        lo = jax.lax.axis_index(ax).astype(jnp.int32) * n_loc
        src_local = (ns >= lo) & (ns < lo + n_loc)
        rows = jnp.where(src_local[:, None],
                         x[jnp.clip(ns - lo, 0, n_loc - 1)], 0)
        rows = jax.lax.psum(rows, ax)
        owned = (nd >= lo) & (nd < lo + n_loc)
        ldst = jnp.where(owned, nd - lo, n_loc)   # n_loc => dropped
        new = x.at[ldst].min(rows, mode="drop")
        return new, jnp.any(new != x, axis=-1)

    sm = shard_map(shard_body, mesh=mesh, check_vma=False,
                   in_specs=(plane_sp, rep, rep),
                   out_specs=(plane_sp, vec_sp))
    return sm(x, jnp.asarray(at_src, jnp.int32),
              jnp.asarray(at_dst, jnp.int32))


@functools.partial(jax.jit, static_argnames=("mesh",))
def sharded_il_rows(il, u: jax.Array, v: jax.Array, *, mesh: Mesh):
    """All-gather-free row reconstruction for the interval verdict path:
    ``(il_out[u], il_out[v], il_in[u], il_in[v])`` as four (Q, 2*dim)
    int32 blocks, rebuilt everywhere from row-sharded planes with ONE
    ``psum`` per batch — the int32 twin of ``sharded_rows``.  The psum is
    exact for any-sign ranks because every in-range row has exactly one
    owner shard.  Out-of-range ids (the engine's dead-lane sentinel
    ``n_cap``) come back as all-zero rows; ``0 > 0`` never holds, so dead
    lanes never prune — and their verdicts are decided by the ``same``
    term anyway, exactly as on the replicated path."""
    il_in, il_out = il
    ax, plane_sp, _, rep = _vspecs(mesh)
    d = int(mesh.devices.size)
    n_loc = il_in.shape[0] // d

    def shard_body(il_in, il_out, u, v):
        lo = jax.lax.axis_index(ax).astype(jnp.int32) * n_loc

        def take(plane, idx):
            local = (idx >= lo) & (idx < lo + n_loc)
            rows = plane[jnp.clip(idx - lo, 0, n_loc - 1)]
            return jnp.where(local[:, None], rows, 0)

        blocks = (take(il_out, u), take(il_out, v),
                  take(il_in, u), take(il_in, v))
        cat = jax.lax.psum(jnp.concatenate(blocks, axis=1), ax)
        w = il_in.shape[1]
        return tuple(cat[:, i * w:(i + 1) * w] for i in range(4))

    sm = shard_map(shard_body, mesh=mesh, check_vma=False,
                   in_specs=(plane_sp, plane_sp, rep, rep),
                   out_specs=(rep,) * 4)
    return sm(il_in, il_out, jnp.asarray(u, jnp.int32),
              jnp.asarray(v, jnp.int32))


@functools.partial(jax.jit, static_argnames=("mesh",))
def sharded_rows(p: Q.PackedLabels, u: jax.Array, v: jax.Array, *,
                 mesh: Mesh) -> Q.RowBlocks:
    """All-gather-free row reconstruction for the verdict path.

    Each shard gathers the (u, v) rows it owns from its local slice of the
    packed planes (zeros for rows it does not own) and ONE ``psum`` per
    batch rebuilds the eight (Q, W) row blocks on every device.  Out-of-
    range ids (the engine's dead-lane sentinel ``n_cap``) come back as
    all-zero rows — they are never owned by any shard."""
    ax, plane_sp, _, rep = _vspecs(mesh)
    d = int(mesh.devices.size)
    n_loc = p.dl_in.shape[0] // d

    def shard_body(dl_in, dl_out, bl_in, bl_out, u, v):
        lo = jax.lax.axis_index(ax).astype(jnp.int32) * n_loc

        def take(plane, idx):
            local = (idx >= lo) & (idx < lo + n_loc)
            rows = plane[jnp.clip(idx - lo, 0, n_loc - 1)]
            return jnp.where(local[:, None], rows, jnp.uint32(0))

        blocks = (take(dl_out, u), take(dl_in, v), take(dl_out, v),
                  take(dl_in, u), take(bl_in, u), take(bl_in, v),
                  take(bl_out, v), take(bl_out, u))
        widths = [b.shape[1] for b in blocks]
        cat = jax.lax.psum(jnp.concatenate(blocks, axis=1), ax)
        outs, off = [], 0
        for w in widths:
            outs.append(cat[:, off:off + w])
            off += w
        return tuple(outs)

    sm = shard_map(shard_body, mesh=mesh, check_vma=False,
                   in_specs=(plane_sp,) * 4 + (rep, rep),
                   out_specs=(rep,) * 8)
    return Q.RowBlocks(*sm(p.dl_in, p.dl_out, p.bl_in, p.bl_out,
                           jnp.asarray(u, jnp.int32),
                           jnp.asarray(v, jnp.int32)))


@functools.partial(jax.jit, static_argnames=("mesh", "max_iters",
                                             "frontier_dtype"))
def _sharded_bfs_impl(p, dlo_u, blin_v, blout_v, u, v, live, m_cut, m_total,
                      dl_clean, e_slot, e_recv, e_gid, e_valid, h_send,
                      h_valid, *, mesh: Mesh, max_iters: int,
                      frontier_dtype: str):
    ax, plane_sp, _, rep = _vspecs(mesh)
    ftype = Q.FRONTIER_DTYPES[frontier_dtype]
    d = int(mesh.devices.size)
    n_cap = p.dl_in.shape[0]
    n_loc = n_cap // d
    H = h_send.shape[2]
    qc = u.shape[0]

    def shard_body(dl_in, bl_in, bl_out, dlo_u, blin_v, blout_v, u, v,
                   live, m_cut, m_total, dl_clean, e_slot, e_recv, e_gid,
                   e_valid, hs, hv):
        e_slot, e_recv, e_gid, e_valid = (a[0] for a in
                                          (e_slot, e_recv, e_gid, e_valid))
        hs, hv = hs[0], hv[0]
        lo = jax.lax.axis_index(ax).astype(jnp.int32) * n_loc
        ids = lo + jnp.arange(n_loc, dtype=jnp.int32)
        # local block of the admit plane (Alg 2 lines 20/22), from the
        # locally-owned plane rows x the psum-reconstructed query rows
        dl_on = (m_cut >= m_total) & dl_clean                    # (Qc,)
        c1 = bitset.subset(bl_in[:, None, :], blin_v[None, :, :])
        c2 = bitset.subset(blout_v[None, :, :], bl_out[:, None, :])
        dterm = bitset.intersect_any(dlo_u[None, :, :], dl_in[:, None, :])
        admit = c1 & c2 & ~(dterm & dl_on[None, :])              # (n_loc, Qc)
        frontier = ids[:, None] == u[None, :]
        visited = frontier
        hit = jnp.zeros((qc,), jnp.bool_)
        owns_v = (v >= lo) & (v < lo + n_loc)
        vloc = jnp.clip(v - lo, 0, n_loc - 1)
        lanes = jnp.arange(qc)

        def body(state):
            fr, visited, hit, it = state
            sf = hv[..., None] & fr[hs]                    # (d, H, Qc)
            rf = jax.lax.all_to_all(sf, ax, 0, 0)
            frc = jnp.concatenate([fr, rf.reshape(d * H, qc)], axis=0)
            contrib = (frc[e_slot] & (live[e_gid] & e_valid)[:, None]
                       & (e_gid[:, None] < m_cut[None, :]))
            nxt = jax.ops.segment_max(contrib.astype(ftype), e_recv,
                                      num_segments=n_loc) > 0
            nxt = nxt & admit & ~visited & ~hit[None, :]
            hit_loc = nxt[vloc, lanes] & owns_v
            hit = hit | (jax.lax.psum(hit_loc.astype(jnp.int32), ax) > 0)
            visited = visited | nxt
            return nxt, visited, hit, it + 1

        def cond(state):
            fr, _, hit, it = state
            alive = jax.lax.psum(fr.sum().astype(jnp.int32), ax) > 0
            return alive & (~hit.all()) & (it < max_iters)

        _, _, hit, _ = jax.lax.while_loop(
            cond, body, (frontier, visited, hit, jnp.int32(0)))
        return hit

    sm = shard_map(
        shard_body, mesh=mesh, check_vma=False,
        in_specs=(plane_sp, plane_sp, plane_sp, rep, rep, rep, rep, rep,
                  rep, rep, rep, rep,
                  plane_sp, plane_sp, plane_sp, plane_sp,
                  P(ax, None, None), P(ax, None, None)),
        out_specs=rep)
    return sm(p.dl_in, p.bl_in, p.bl_out, dlo_u, blin_v, blout_v, u, v,
              live, m_cut, m_total, dl_clean, e_slot, e_recv, e_gid,
              e_valid, h_send, h_valid)


def sharded_pruned_bfs(plan: ShardPlan, p: Q.PackedLabels,
                       rows: Q.RowBlocks, u: jax.Array, v: jax.Array,
                       live: jax.Array, m_cut: jax.Array,
                       m_total: jax.Array, dl_clean: jax.Array, *,
                       max_iters: int = 256,
                       frontier_dtype: str = "int8") -> jax.Array:
    """(Qc,) bool — vertex-sharded twin of ``query.pruned_bfs``.

    The admit, frontier, and visited planes stay row-sharded; each round
    exchanges only the boundary frontier *bits* (one all_to_all over the
    plan's cut-edge routing) plus two scalar-ish psums (global frontier
    liveness, per-lane hit bits).  Per-lane edge-count cutoffs and the DL
    prune gate behave exactly as in the replicated BFS, so hits are
    bitwise identical.  Dead lanes carry ``u == n_cap``: no shard owns that
    id, so their frontier starts (and stays) empty."""
    return _sharded_bfs_impl(
        p, rows.dlo_u, rows.blin_v, rows.blout_v,
        jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32), live,
        jnp.asarray(m_cut, jnp.int32), jnp.asarray(m_total, jnp.int32),
        jnp.asarray(dl_clean, jnp.bool_),
        plan.fwd.e_slot, plan.fwd.e_recv, plan.fwd.e_gid, plan.fwd.e_valid,
        plan.fwd.h_send, plan.fwd.h_valid,
        mesh=plan.mesh, max_iters=max_iters, frontier_dtype=frontier_dtype)

"""DBLIndex — the public API of the paper's contribution.

    idx = DBLIndex.build(g, n_cap=..., k=64, k_prime=64)
    ans = idx.query(u, v)                  # Alg 2
    idx = idx.insert_edges(src, dst)       # Alg 3 (batched)
    idx = idx.delete_edges(src, dst)       # tombstones + dirty flag (cheap)
    idx = idx.rebuild(mode="auto")         # lazy label rebuild over live edges

The index is a pytree (usable under jit / pjit / checkpointing).  Bool planes
are the mutable source of truth; packed uint32 words are kept in sync and feed
the query path + Pallas kernels.

**Fully-dynamic mode.**  Deletions never touch a DAG and never recompute
labels eagerly: ``delete_edges`` stamps epoch-versioned tombstones on the
graph and leaves the labels as a sound *over-approximation* (deletions only
shrink reachability).  While ``dirty`` (``graph.del_epoch`` is ahead of
``label_del_epoch``, the delete epoch the labels were last rebuilt for),
queries downgrade every verdict that rests on positive label evidence — DL
positives and the theorem-1/2 negatives — to "unknown -> BFS over live
edges", while BL-containment negatives stay valid (they only need label
completeness, and bits are never removed).  ``rebuild`` clears the dirty
state and bumps the snapshot epoch; ``mode="full"`` re-runs Alg 1 over the
live edge set, ``mode="delta"`` repairs only the label state a deleted edge
could have invalidated, ``mode="auto"`` picks by invalidation estimate.

**Delta rebuild.**  A full Alg-1 rebuild re-derives every label bit even
though most of them are still exact — the whole-index recomputation cost
DBL's landmark/leaf design exists to avoid.  The delta path instead:

1. computes the *invalidation frontier*: the closure of the tombstoned
   edges' heads (tails, for the out-planes) over the edge set the labels
   were last built against (``propagate.reach_mask``) — any label bit that
   was derived through a deleted edge (u, v) certifies a path whose suffix
   starts at v, so its owner is in reach(v);
2. diffs the seed sets a fresh Alg 1 would use: landmarks are re-selected
   and matched by IDENTITY (rank swaps keep their columns), leaf masks are
   re-derived and diffed per hash bucket — changed lanes/buckets become
   *fresh columns*, rebuilt from scratch (a removed seed cannot be
   subtracted from a monotone plane);
3. resets exactly the invalidated entries (dirty rows ∪ fresh columns) to
   their Alg-1 seed values and re-runs the monotone fixpoint from the
   dirty boundary, relaxing only live edges that point INTO the dirty
   region (pushes into clean vertices are provably no-ops).

Because the reset state X satisfies seeds <= X <= lfp(seeds) and the clean
region is already edge-wise absorbed, the monotone fixpoint from X reaches
the SAME least fixpoint Alg 1 reaches from the seeds alone: delta labels
are bitwise equal to a full rebuild's (tests/test_delta_rebuild.py pins
this property across random interleaved streams).  A saturated index falls
back to a full rebuild — truncated labels are not a sound starting state,
and reusing them could launder missing bits into ``saturated=False``.

**Pytree dtype discipline.**  ``epoch`` / ``label_del_epoch`` are always
int32 scalars and ``saturated`` a bool scalar *as jax.Arrays* from
construction on — a leaf that flips between a weak-typed Python int and a
traced array changes the pytree's aval and forces jit retraces (and breaks
checkpoint/restore round-trips), so every construction path normalizes.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from . import bitset
from . import families as F
from . import graph as G
from . import labels as L
from . import planes as PL
from . import propagate as P
from . import query as Q
from . import select as S
from . import update as U


class LabelSaturationWarning(UserWarning):
    """An insert's label fixpoint hit max_iters without converging — labels
    are stale and queries may return FALSE negatives until a rebuild."""


class LabelSaturationError(RuntimeError):
    """Strict-mode variant of LabelSaturationWarning."""


def _saturation_message(max_iters) -> str:
    return (f"label propagation hit max_iters={max_iters} without "
            "converging: labels are stale and queries may return wrong "
            "answers. Re-run with a larger max_iters or rebuild() the index.")


def _host_reach(src: np.ndarray, dst: np.ndarray, live: np.ndarray,
                seeds: np.ndarray) -> np.ndarray:
    """(n_cap,) bool — host-side reachability closure of ``seeds`` over the
    ``live`` edges (inclusive).  The CPU-backend twin of
    ``propagate.reach_mask``: a level-synchronous boolean-scatter BFS costs
    O(m) numpy work per level with no dispatch overhead, which on CPU beats
    the device fixpoint's per-round fixed costs by an order of magnitude —
    the delta plan picks per backend."""
    reach = seeds.copy()
    frontier = seeds.copy()
    n = seeds.shape[0]
    while frontier.any():
        hit = np.zeros(n, bool)
        hit[dst[live & frontier[src]]] = True
        frontier = hit & ~reach
        reach |= frontier
    return reach


class DBLIndex(NamedTuple):
    graph: G.Graph
    landmarks: jax.Array        # (k,) int32
    dl_in: jax.Array            # (n_cap, k)  uint8 plane
    dl_out: jax.Array
    bl_in: jax.Array            # (n_cap, k') uint8 plane
    bl_out: jax.Array
    packed: Q.PackedLabels      # uint32 word views
    # the leaf masks the BL planes were seeded with (build-time membership;
    # inserts propagate but never re-seed).  The delta rebuild diffs these
    # against the live graph's masks to find churned hash buckets — they are
    # the BL analogue of the stored ``landmarks`` vector.
    bl_sources: jax.Array       # (n_cap,) bool
    bl_sinks: jax.Array         # (n_cap,) bool
    # snapshot epoch: bumped by every insert AND delete batch.  Within one
    # delete epoch, (epoch, graph.m) names the exact edge set this index
    # snapshot observed — the serving engine keys cross-snapshot BFS
    # coalescing off it.
    epoch: jax.Array | int = 0
    # the graph delete-epoch the labels were last (re)built for; labels are
    # dirty (deletion-stale) whenever graph.del_epoch is ahead of this
    label_del_epoch: jax.Array | int = 0
    # sticky flag: some insert's label fixpoint hit max_iters (stale labels)
    saturated: jax.Array | bool = False
    # plug-in family storage (core.families registry).  The fused DL/BL
    # core above is mandatory; plug-ins append optional trailing fields so
    # the default-families pytree carries EXACTLY the pre-registry leaves
    # (None fields flatten to nothing — no aval churn, no retraces).  The
    # "il" family: (n_cap, 2*dim) int32 [lo | -hi] interval planes per
    # direction plus the committed int32 rank seed they re-derive from.
    il_in: jax.Array | None = None
    il_out: jax.Array | None = None
    il_seed: jax.Array | None = None

    # ---- static helpers -------------------------------------------------
    @property
    def n_cap(self) -> int:
        return self.dl_in.shape[0]

    @property
    def k(self) -> int:
        return self.dl_in.shape[1]

    @property
    def k_prime(self) -> int:
        return self.bl_in.shape[1]

    @property
    def families(self) -> tuple[str, ...]:
        """Enabled label families, derived from what the index stores."""
        return F.CORE_FAMILIES + (("il",) if self.il_in is not None else ())

    @property
    def il(self):
        """(il_in, il_out) verdict-path operand pytree, or None.  None has
        no pytree leaves, so default-families executables trace the exact
        pre-registry programs."""
        return None if self.il_in is None else (self.il_in, self.il_out)

    @property
    def il_dim(self) -> int | None:
        return None if self.il_in is None else self.il_in.shape[-1] // 2

    @property
    def store(self) -> PL.PlaneStore:
        """Zero-copy PlaneStore view of the label state (planes + landmarks
        + BL leaf masks).  The store is where layout-aware operations live;
        the flat index fields remain the serving pytree.  Layout is derived
        from where the rows actually are: a plane device_put along a vertex
        mesh reports ``vertex_sharded``."""
        return PL.PlaneStore(self.dl_in, self.dl_out, self.bl_in,
                             self.bl_out, self.landmarks, self.bl_sources,
                             self.bl_sinks, layout=PL.layout_of(self.dl_in))

    def with_store(self, store: PL.PlaneStore, **kw) -> "DBLIndex":
        """Rebuild the flat index fields from a store (re-packs words)."""
        return self._replace(
            dl_in=store.dl_in, dl_out=store.dl_out, bl_in=store.bl_in,
            bl_out=store.bl_out, landmarks=store.landmarks,
            bl_sources=store.bl_sources, bl_sinks=store.bl_sinks,
            packed=store.pack(), **kw)

    @property
    def dirty_flag(self) -> jax.Array:
        """() bool (traced-friendly): labels carry un-rebuilt deletions."""
        return self.graph.del_epoch > jnp.asarray(self.label_del_epoch,
                                                  jnp.int32)

    @property
    def is_dirty(self) -> bool:
        """Host-side dirty check (syncs one scalar)."""
        return bool(np.asarray(self.dirty_flag))

    # ---- construction (Alg 1) -------------------------------------------
    @staticmethod
    def build(g: G.Graph, *, n_cap: int, k: int = 64, k_prime: int = 64,
              selection: str = "product", leaf_r: int = 0,
              max_iters: int = 256, check: str = "warn",
              plane_repr: str = "bool",
              families=F.DEFAULT_FAMILIES, il_dim: int = F.DEFAULT_IL_DIM,
              il_seed: int = 0) -> "DBLIndex":
        """Alg 1.  A build whose fixpoints hit ``max_iters`` without
        converging produces INCOMPLETE labels (same failure mode as a
        saturated insert): the ``saturated`` flag is set and ``check``
        behaves as in ``insert_edges`` ("warn" default / "raise" /
        "defer").  ``plane_repr="packed"`` runs every fixpoint on
        uint32-packed word planes (bitwise-equal labels, 32 lanes/word).

        ``families`` enables label families beyond the fused DL/BL core
        (``core.families`` registry); each plug-in builds through its own
        hooks in its own plane repr.  ``il_dim``/``il_seed`` parameterize
        the interval family when enabled."""
        return DBLIndex._build(
            g, n_cap=n_cap, k=k, k_prime=k_prime, selection=selection,
            leaf_r=leaf_r, max_iters=max_iters, check=check,
            plane_repr=plane_repr, families=families, il_dim=il_dim,
            il_seed=il_seed)[0]

    @staticmethod
    def _build(g: G.Graph, *, n_cap: int, k: int = 64, k_prime: int = 64,
               selection: str = "product", leaf_r: int = 0,
               max_iters: int = 256, check: str = "warn",
               plane_repr: str = "bool", families=F.DEFAULT_FAMILIES,
               il_dim: int = F.DEFAULT_IL_DIM, il_seed: int = 0
               ) -> tuple["DBLIndex", jax.Array]:
        """``build`` plus its fixpoints' relaxation rounds, summed (a ()
        int32 on the device, ``update.rounds``)."""
        if check not in ("warn", "raise", "defer"):
            raise ValueError(f"unknown check mode {check!r}")
        P.check_plane_repr(plane_repr)
        plugin_fams = F.plugins(families)
        landmarks = S.select_landmarks(g, n_cap=n_cap, k=k, method=selection)
        dl_in, dl_out, it_dl = L.build_dl(g, landmarks, n_cap=n_cap, k=k,
                                          max_iters=max_iters,
                                          plane_repr=plane_repr)
        sources, sinks = S.leaf_masks(g, n_cap=n_cap, leaf_r=leaf_r)
        bl_in, bl_out, it_bl = L.build_bl(g, sources, sinks, n_cap=n_cap,
                                          k_prime=k_prime,
                                          max_iters=max_iters,
                                          plane_repr=plane_repr)
        all_iters = [it_dl, it_bl]
        extra = {}
        for fam in plugin_fams:
            p_in, p_out, it_f = fam.build(g, n_cap=n_cap, dim=il_dim,
                                          seed=il_seed, max_iters=max_iters)
            extra[fam.name] = (p_in, p_out)
            all_iters.append(it_f)
        sat = U.saturated(jnp.concatenate(all_iters), max_iters)
        if check != "defer" and bool(np.asarray(sat)):
            if check == "raise":
                raise LabelSaturationError(_saturation_message(max_iters))
            warnings.warn(_saturation_message(max_iters),
                          LabelSaturationWarning, stacklevel=2)
        packed = Q.pack_labels(dl_in, dl_out, bl_in, bl_out)
        il = extra.get("il")
        # NB: a real copy, not asarray — label_del_epoch must not alias the
        # graph's del_epoch buffer (the engine's insert path donates the
        # graph; an aliased leaf would be invalidated with it)
        idx = DBLIndex(g, landmarks, dl_in, dl_out, bl_in, bl_out, packed,
                       sources, sinks,
                       epoch=jnp.int32(0),
                       label_del_epoch=jnp.array(g.del_epoch, jnp.int32),
                       saturated=sat,
                       il_in=None if il is None else il[0],
                       il_out=None if il is None else il[1],
                       il_seed=None if il is None else jnp.int32(il_seed))
        return idx, U.rounds(jnp.concatenate(all_iters), max_iters)

    # ---- queries (Alg 2) --------------------------------------------------
    def query(self, u, v, *, bfs_chunk: int = 64, max_iters: int = 256,
              return_stats: bool = False, driver: str = "engine"):
        """Batched reachability.  ``driver="engine"`` (default) runs the
        device-resident QueryEngine (fused label phase + compacted BFS
        chunks); ``driver="host"`` runs the original host-side loop, kept
        as the reference implementation for differential testing.  Both
        drivers honor the dirty (deletion-stale) state."""
        if driver == "host":
            return Q.query(self.graph, self.packed, u, v, n_cap=self.n_cap,
                           bfs_chunk=bfs_chunk, max_iters=max_iters,
                           return_stats=return_stats, dirty=self.is_dirty,
                           il=self.il)
        if driver != "engine":
            raise ValueError(f"unknown driver {driver!r}")
        from repro.serve.engine import engine_for  # lazy: core <-> serve
        eng = engine_for(bfs_chunk=bfs_chunk, max_iters=max_iters)
        return eng.run(self, u, v, return_stats=return_stats)

    def label_verdicts(self, u, v):
        return Q.label_verdicts(self.packed, jnp.asarray(u, jnp.int32),
                                jnp.asarray(v, jnp.int32), il=self.il)

    # ---- updates (Alg 3) --------------------------------------------------
    def insert_edges(self, new_src, new_dst, *, max_iters: int = 256,
                     check: str = "warn",
                     plane_repr: str = "bool") -> "DBLIndex":
        """Batched Alg-3 insert.  ``check`` controls saturation handling —
        the fixpoint's iteration vector is NOT discarded: if any label
        plane hit ``max_iters`` without converging the labels are silently
        stale, so ``"warn"`` (default) syncs the one-bit flag and warns,
        ``"raise"`` raises ``LabelSaturationError`` (strict mode), and
        ``"defer"`` skips the host sync and only folds the flag into the
        index's sticky ``saturated`` field (the serving engine uses this
        and checks at flush boundaries)."""
        if check not in ("warn", "raise", "defer"):
            raise ValueError(f"unknown check mode {check!r}")
        new_src = jnp.asarray(new_src, jnp.int32)
        new_dst = jnp.asarray(new_dst, jnp.int32)
        g2, dl_in, dl_out, bl_in, bl_out, iters, epoch2 = U.insert_and_update(
            self.graph, self.dl_in, self.dl_out, self.bl_in, self.bl_out,
            new_src, new_dst, self.epoch, n_cap=self.n_cap,
            max_iters=max_iters, plane_repr=plane_repr)
        sat_now = U.saturated(iters, max_iters)
        il_kw = {}
        for fam in F.plugins(self.families):
            il_in, il_out, it_f = U.insert_update_plugin(
                fam.name, g2, self.il_in, self.il_out, new_src, new_dst,
                n_cap=self.n_cap, max_iters=max_iters)
            il_kw = dict(il_in=il_in, il_out=il_out)
            sat_now = sat_now | U.saturated(it_f, max_iters)
        if check != "defer" and bool(np.asarray(sat_now)):
            if check == "raise":
                raise LabelSaturationError(_saturation_message(max_iters))
            warnings.warn(_saturation_message(max_iters),
                          LabelSaturationWarning, stacklevel=2)
        packed = Q.pack_labels(dl_in, dl_out, bl_in, bl_out)
        return self._replace(
            graph=g2, dl_in=dl_in, dl_out=dl_out, bl_in=bl_in, bl_out=bl_out,
            packed=packed, epoch=epoch2,
            saturated=jnp.asarray(self.saturated) | sat_now, **il_kw)

    def delete_edges(self, del_src, del_dst) -> "DBLIndex":
        """Tombstone every live edge matching a (src, dst) pair — O(m) mask
        work, NO label recomputation.  The returned index is dirty: queries
        downgrade label positives / theorem negatives to live-edge BFS until
        ``rebuild()`` (BL negatives stay sound; see module docstring)."""
        g2, epoch2 = U.delete_and_mark(
            self.graph, jnp.asarray(del_src, jnp.int32),
            jnp.asarray(del_dst, jnp.int32), self.epoch)
        return self._replace(graph=g2, epoch=epoch2)

    def rebuild(self, *, mode: str = "full", selection: str = "product",
                leaf_r: int = 0, max_iters: int = 256, compact: bool = True,
                check: str = "warn", delta_threshold: float = 0.99,
                plane_repr: str = "bool") -> "DBLIndex":
        """Lazy label rebuild over the LIVE edge set, clearing the dirty
        state.  ``mode`` selects the maintenance path:

        - ``"full"`` — re-run Alg 1 from scratch (the PR-3 behavior);
        - ``"delta"`` — repair only the label state a tombstoned edge (or
          landmark/leaf churn) could have invalidated, re-running the
          fixpoint from the invalidation frontier; bitwise equal to a full
          rebuild (see module docstring).  Falls back to full when the
          index is ``saturated`` (stale labels are not a sound delta base);
        - ``"auto"`` — delta when the estimated invalidated label fraction
          is at most ``delta_threshold``, full otherwise.  The default
          threshold is deliberately permissive (0.99): the delta executor's
          fused single pass per direction is structurally cheaper than the
          four separate Alg-1 fixpoints even under broad invalidation
          (BENCH_PR4: delta won at every measured fraction up to 0.99), so
          the estimate gate only catches the degenerate everything-changed
          case where a delta is pure overhead.

        The ``saturated`` flag comes out reflecting THIS rebuild's
        convergence (a rebuild whose own fixpoints are cut off at
        ``max_iters`` is just as stale as a saturated insert — ``check``
        surfaces it, as in ``build``).  ``compact=True`` also squeezes
        tombstones out of the edge arrays, reclaiming capacity; slot
        renumbering is safe here because a rebuild starts a new snapshot
        lineage (the serving engine re-binds and resolves in-flight batches
        first).  The snapshot epoch keeps increasing monotonically across
        the rebuild."""
        return self.rebuild_info(
            mode=mode, selection=selection, leaf_r=leaf_r,
            max_iters=max_iters, compact=compact, check=check,
            delta_threshold=delta_threshold, plane_repr=plane_repr)[0]

    def rebuild_info(self, *, mode: str = "full", selection: str = "product",
                     leaf_r: int = 0, max_iters: int = 256,
                     compact: bool = True, check: str = "warn",
                     delta_threshold: float = 0.99,
                     plane_repr: str = "bool"
                     ) -> tuple["DBLIndex", dict]:
        """``rebuild`` plus a report of what actually ran: ``(index, info)``
        where ``info["mode"]`` is the executed path (``"delta"``/``"full"``),
        ``info["reason"]`` one of ``"forced"``/``"estimate"``/``"saturated"``,
        ``info["rounds"]`` the relaxation rounds its label fixpoints ran
        (summed, a () int32 on the device: reading it waits for them),
        and — whenever a delta plan was computed — ``info["estimate"]`` the
        invalidation estimate the auto policy keys off.  The serving layer
        uses this to account delta vs full rebuilds separately."""
        if mode not in ("full", "delta", "auto"):
            raise ValueError(f"unknown rebuild mode {mode!r}")
        full_kw = dict(selection=selection, leaf_r=leaf_r,
                       max_iters=max_iters, compact=compact, check=check,
                       plane_repr=plane_repr)

        # each step reads device results back between its host steps, so
        # its span is a wait (``repro.serve.spans``)
        def full(**info):
            with span("index.rebuild.wait_fixpoint"):
                idx, rounds = self._full_rebuild(**full_kw)
            return idx, {"mode": "full", **info, "rounds": rounds}

        if mode == "full":
            return full(reason="forced")
        with span("index.rebuild.wait_sat"):
            saturated = bool(np.asarray(self.saturated))
        if saturated:
            # a saturated index's labels are missing bits in an unknown
            # pattern: neither the clean region nor the invalidation
            # closure can be trusted, and a delta from them could launder
            # stale labels into saturated=False.  Rebuild honestly.
            return full(reason="saturated")
        with span("index.rebuild.wait_plan"):
            plan = self._delta_plan(selection=selection, leaf_r=leaf_r)
        est = plan["estimate"]
        if mode == "auto" and est["frac"] > delta_threshold:
            return full(reason="estimate", estimate=est)
        with span("index.rebuild.wait_fixpoint"):
            idx, rounds = self._delta_rebuild(plan, max_iters=max_iters,
                                              compact=compact, check=check,
                                              plane_repr=plane_repr)
        reason = "forced" if mode == "delta" else "estimate"
        return idx, {"mode": "delta", "reason": reason, "estimate": est,
                     "rounds": rounds}

    def _full_rebuild(self, *, selection: str, leaf_r: int, max_iters: int,
                      compact: bool, check: str, plane_repr: str = "bool"
                      ) -> tuple["DBLIndex", jax.Array]:
        """(index, rounds): Alg 1 over the live edges."""
        g = G.compact(self.graph) if compact else self.graph
        fam_kw = {}
        if self.il_in is not None:
            fam_kw = dict(families=self.families, il_dim=self.il_dim,
                          il_seed=self.il_seed)
        idx, rounds = DBLIndex._build(
            g, n_cap=self.n_cap, k=self.k, k_prime=self.k_prime,
            selection=selection, leaf_r=leaf_r, max_iters=max_iters,
            check=check, plane_repr=plane_repr, **fam_kw)
        return idx._replace(
            epoch=jnp.asarray(self.epoch, jnp.int32) + jnp.int32(1)), rounds

    def _delta_plan(self, *, selection: str, leaf_r: int) -> dict:
        """Compute the invalidation frontier, the re-selected seed sets,
        the fresh-column masks, and the invalidation estimate.  Cheap next
        to a rebuild: two single-lane closures plus O(n + m) seed work —
        the auto policy pays this to decide delta vs full.  The O(n_cap *
        (k + k')) partially-reset planes are NOT built here; ``_delta_
        rebuild`` assembles them only once the delta path is chosen."""
        g = self.graph
        n_cap, k, kp = self.n_cap, self.k, self.k_prime
        lde = jnp.asarray(self.label_del_epoch, jnp.int32)
        # the edge set the labels are an exact fixpoint over: everything
        # live now PLUS everything tombstoned since the last (re)build
        old_live = G.edge_mask(g, lde)
        old_live_np = np.asarray(old_live)
        deleted_np = np.asarray(G.deleted_since(g, lde))
        s_np = np.asarray(g.src)
        d_np = np.asarray(g.dst)
        seeds_f = np.zeros(n_cap, bool)
        seeds_f[d_np[deleted_np]] = True
        seeds_b = np.zeros(n_cap, bool)
        seeds_b[s_np[deleted_np]] = True
        if jax.default_backend() == "cpu":
            dirty_fwd_np = _host_reach(s_np, d_np, old_live_np, seeds_f)
            dirty_bwd_np = _host_reach(d_np, s_np, old_live_np, seeds_b)
        else:
            # max_iters=n_cap: a frontier BFS over n_cap vertices always
            # converges within n_cap rounds — the closure never truncates
            dirty_fwd_np = np.asarray(P.reach_mask(
                g.src, g.dst, old_live, jnp.asarray(seeds_f),
                n_cap=n_cap, max_iters=n_cap)[0])
            dirty_bwd_np = np.asarray(P.reach_mask(
                g.src, g.dst, old_live, jnp.asarray(seeds_b),
                n_cap=n_cap, max_iters=n_cap, reverse=True)[0])
        dirty_fwd = jnp.asarray(dirty_fwd_np)
        dirty_bwd = jnp.asarray(dirty_bwd_np)
        landmarks = S.select_landmarks(g, n_cap=n_cap, k=k, method=selection)
        sources, sinks = S.leaf_masks(g, n_cap=n_cap, leaf_r=leaf_r)
        # fresh-column masks only (O(k^2 + n)) — the full plane assembly
        # waits until the delta path is actually chosen
        dl_fresh = ~jnp.any(landmarks[:, None] == self.landmarks[None, :],
                            axis=1)
        fresh_fwd = np.concatenate([
            np.asarray(dl_fresh),
            np.asarray(L.bucket_churn(self.bl_sources, sources,
                                      k_prime=kp))])
        fresh_bwd = np.concatenate([
            np.asarray(dl_fresh),
            np.asarray(L.bucket_churn(self.bl_sinks, sinks, k_prime=kp))])
        n = max(int(np.asarray(g.n)), 1)
        rf = float(dirty_fwd_np.sum()) / n
        rb = float(dirty_bwd_np.sum()) / n
        # invalidated-entry fraction per plane (rows ∪ columns), worst case
        # over the four planes — the auto policy's threshold input
        def plane_frac(r, c):
            return r + c - r * c
        fracs = {
            "dl_in": plane_frac(rf, float(fresh_fwd[:k].mean())),
            "dl_out": plane_frac(rb, float(fresh_bwd[:k].mean())),
            "bl_in": plane_frac(rf, float(fresh_fwd[k:].mean())),
            "bl_out": plane_frac(rb, float(fresh_bwd[k:].mean())),
        }
        estimate = {
            "frac": max(fracs.values()),
            "plane_fracs": fracs,
            "dirty_fwd": int(dirty_fwd_np.sum()),
            "dirty_bwd": int(dirty_bwd_np.sum()),
            "fresh_cols_fwd": int(fresh_fwd.sum()),
            "fresh_cols_bwd": int(fresh_bwd.sum()),
            "dead_edges": int(np.asarray(G.dead_edge_count(g))),
        }
        return {"dirty_fwd": dirty_fwd_np, "dirty_bwd": dirty_bwd_np,
                "dirty_fwd_j": dirty_fwd, "dirty_bwd_j": dirty_bwd,
                "landmarks": landmarks, "sources": sources, "sinks": sinks,
                "estimate": estimate}

    def _delta_rebuild(self, plan: dict, *, max_iters: int, compact: bool,
                       check: str, plane_repr: str = "bool"
                       ) -> tuple["DBLIndex", jax.Array]:
        """Execute a delta plan: ONE fused fixpoint per propagation
        direction.

        With fresh columns (landmark/leaf churn) the pass runs over the
        full live edge set — fresh seeds join the frontier, so churned
        lanes rebuild from scratch in the same relaxation rounds that
        repair the dirty region.  Without churn the pass relaxes only the
        live edges that point INTO the dirty region (pushes into clean
        vertices are provably no-ops: their rows are final and edge-wise
        absorbed), gathered into a padded bucket so compiled shapes stay
        bounded.  Either way the monotone fixpoint from the partially-reset
        state converges to the same least fixpoint a full Alg 1 reaches."""
        if check not in ("warn", "raise", "defer"):
            raise ValueError(f"unknown check mode {check!r}")
        g = self.graph
        n_cap, k = self.n_cap, self.k
        live = G.edge_mask(g)
        live_np = np.asarray(live)
        s_np = np.asarray(g.src)
        d_np = np.asarray(g.dst)
        m_cap = s_np.shape[0]
        (x_fwd, x_bwd, fresh_fwd, fresh_bwd, seed_fwd, seed_bwd,
         fr_fwd, fr_bwd) = L.delta_plane_state(
            g, self.dl_in, self.dl_out, self.bl_in, self.bl_out,
            self.landmarks, plan["landmarks"], self.bl_sources,
            self.bl_sinks, plan["sources"], plan["sinks"],
            plan["dirty_fwd_j"], plan["dirty_bwd_j"],
            n_cap=n_cap, k=k, k_prime=self.k_prime)
        iters = []

        def sub_arrays(sel):
            size = 1024
            while size < sel.size:
                size <<= 1
            if size >= m_cap:
                return g.src, g.dst, live
            ss = np.zeros(size, np.int32)
            dd = np.zeros(size, np.int32)
            lv = np.zeros(size, bool)
            ss[:sel.size] = s_np[sel]
            dd[:sel.size] = d_np[sel]
            lv[:sel.size] = True
            return jnp.asarray(ss), jnp.asarray(dd), jnp.asarray(lv)

        def run_direction(x, seed, fresh, dirty, frontier, reverse):
            target_np = s_np if reverse else d_np
            has_fresh = bool(np.asarray(fresh).any())
            if has_fresh:
                # fresh seeds must reach everywhere: relax the full live
                # edge set, with the churned lanes' seed vertices pushing
                # alongside the dirty boundary
                fr = frontier | (seed & fresh[None, :]).any(axis=1)
                es, ed, el = g.src, g.dst, live
            else:
                sel = np.flatnonzero(live_np & np.asarray(dirty)[target_np])
                fr = frontier
                es, ed, el = sub_arrays(sel)
            x, it = P.propagate(x, es, ed, el, fr, n_cap=n_cap,
                                monoid="or", max_iters=max_iters,
                                reverse=reverse, plane_repr=plane_repr)
            iters.append(it)
            return x

        x_fwd = run_direction(x_fwd, seed_fwd, fresh_fwd, plan["dirty_fwd"],
                              fr_fwd, False)
        x_bwd = run_direction(x_bwd, seed_bwd, fresh_bwd, plan["dirty_bwd"],
                              fr_bwd, True)
        g2 = G.compact(g) if compact else g
        # plug-in family repair: under deletion every interval dimension is
        # churned (min planes are not per-column decomposable), so the IL
        # hook re-derives both planes from the stored seed over the live
        # edge set — deterministic in (seed, n_cap, dim), hence bitwise
        # equal to what a full rebuild would produce
        il_in = il_out = None
        for fam in F.plugins(self.families):
            il_in, il_out, it_f = fam.rebuild(
                g2, n_cap=n_cap, dim=self.il_dim, seed=self.il_seed,
                max_iters=max_iters)
            iters.append(it_f)
        sat = U.saturated(
            jnp.concatenate([jnp.atleast_1d(i) for i in iters]), max_iters)
        if check != "defer" and bool(np.asarray(sat)):
            if check == "raise":
                raise LabelSaturationError(_saturation_message(max_iters))
            warnings.warn(_saturation_message(max_iters),
                          LabelSaturationWarning, stacklevel=3)
        dl_in, bl_in = x_fwd[:, :k], x_fwd[:, k:]
        dl_out, bl_out = x_bwd[:, :k], x_bwd[:, k:]
        packed = Q.pack_labels(dl_in, dl_out, bl_in, bl_out)
        return DBLIndex(
            g2, plan["landmarks"], dl_in, dl_out, bl_in, bl_out, packed,
            plan["sources"], plan["sinks"],
            epoch=jnp.asarray(self.epoch, jnp.int32) + jnp.int32(1),
            label_del_epoch=jnp.array(g2.del_epoch, jnp.int32),
            saturated=sat, il_in=il_in, il_out=il_out,
            il_seed=self.il_seed), U.rounds(
                jnp.concatenate([jnp.atleast_1d(i) for i in iters]),
                max_iters)

    # ---- introspection ----------------------------------------------------
    def label_bytes(self) -> int:
        return sum(int(w.size) * 4 for w in self.packed)

    def density(self) -> dict:
        return {
            "dl_in": float(bitset.popcount(self.packed.dl_in).mean()),
            "dl_out": float(bitset.popcount(self.packed.dl_out).mean()),
            "bl_in": float(bitset.popcount(self.packed.bl_in).mean()),
            "bl_out": float(bitset.popcount(self.packed.bl_out).mean()),
        }

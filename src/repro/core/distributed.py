"""Mesh-sharded DBL: vertex-partitioned label planes, edge-sharded relaxation.

Two sharding regimes coexist here:

**GSPMD scheme** (the original; DESIGN.md §6) — shardings injected at the
jit boundary and the SPMD partitioner materializes whatever exchanges the
unmodified core/ code needs (including label all-gathers on the query
path).  Kept for elasticity tests and as the auto-partitioned reference:
- label planes (n_cap, k): n → every mesh axis (flattened);
- edge arrays (m_cap,):    m → same axes;
- query batches (Q,):      Q → axes (embarrassingly parallel fast path).

**Vertex-sharded scheme** (``build_vertex_sharded`` & co) — the layout
``core.planes`` implements with hand-written collectives: label planes are
row-partitioned along a 1-axis ``"vertex"`` mesh (per-device label bytes =
1/shards of replicated), the graph/landmarks/scalars stay replicated
(O(m + k) ints — cheap next to O(n·(k+k')) planes), and every lifecycle
path runs shard-local with explicit halo exchanges: fixpoints move only
boundary frontier rows (``planes.halo_propagate``), verdicts reconstruct
only the (Q, W) row blocks with one psum (``planes.sharded_rows``), BFS
residues exchange only boundary frontier bits — no label all-gather
anywhere.  All results are bitwise identical to the replicated index.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import families as F
from . import graph as G
from . import labels as L
from . import planes as PL
from . import query as Q
from . import select as S
from . import update as U
from .dbl import (DBLIndex, LabelSaturationError, LabelSaturationWarning,
                  _saturation_message)
from .graph import Graph


def _axes(mesh: Mesh) -> tuple:
    return tuple(mesh.axis_names)


def index_shardings(mesh: Mesh, *, il: bool = False) -> DBLIndex:
    """A DBLIndex-shaped pytree of NamedShardings.  ``il=True`` adds the
    interval plug-in family's leaves — (n_cap, 2*dim) int32 rank planes
    sharded like the bool planes, plus the replicated scalar seed; the
    default keeps the trailing fields None so the pytree matches a
    default-families index exactly."""
    ax = _axes(mesh)
    vec = NamedSharding(mesh, P(ax))          # (n,) / (m,) arrays
    plane = NamedSharding(mesh, P(ax, None))  # (n, k) planes
    scal = NamedSharding(mesh, P())
    g = Graph(src=vec, dst=vec, n=scal, m=scal, del_at=vec, del_epoch=scal)
    packed = Q.PackedLabels(plane, plane, plane, plane)
    return DBLIndex(graph=g, landmarks=scal, dl_in=plane, dl_out=plane,
                    bl_in=plane, bl_out=plane, packed=packed,
                    bl_sources=vec, bl_sinks=vec, epoch=scal,
                    label_del_epoch=scal, saturated=scal,
                    il_in=plane if il else None,
                    il_out=plane if il else None,
                    il_seed=scal if il else None)


def shard_index(idx: DBLIndex, mesh: Mesh) -> DBLIndex:
    """device_put every leaf with the scheme above (elastic re-placement)."""
    sh = index_shardings(mesh, il=idx.il_in is not None)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), idx, sh)


def distributed_build(g: Graph, mesh: Mesh, *, n_cap: int, k: int = 64,
                      k_prime: int = 64, **kw) -> DBLIndex:
    """Build on sharded inputs; label planes come out vertex-partitioned."""
    g = jax.device_put(g, index_shardings(mesh).graph)
    idx = DBLIndex.build(g, n_cap=n_cap, k=k, k_prime=k_prime, **kw)
    return shard_index(idx, mesh)


def distributed_label_verdicts(idx: DBLIndex, mesh: Mesh, u, v):
    """Fast-path verdicts with the query batch sharded across the mesh."""
    ax = _axes(mesh)
    qsh = NamedSharding(mesh, P(ax))
    u = jax.device_put(jnp.asarray(u, jnp.int32), qsh)
    v = jax.device_put(jnp.asarray(v, jnp.int32), qsh)
    fn = jax.jit(Q.label_verdicts, out_shardings=qsh)
    return fn(idx.packed, u, v, idx.il)


@functools.lru_cache(maxsize=16)
def _sharded_insert_fn(mesh: Mesh, n_cap: int, max_iters: int):
    """Jitted Alg-3 insert with the index sharding scheme injected at the
    jit boundary: inputs arrive in their resident shardings (no reshuffle),
    outputs are CONSTRAINED to the same scheme, so the sharded index never
    round-trips through the host between insert batches.  Cached per
    (mesh, n_cap, max_iters) so repeated inserts reuse one executable."""
    sh = index_shardings(mesh)
    plane = sh.dl_in
    repl = NamedSharding(mesh, P())

    def impl(g, dl_in, dl_out, bl_in, bl_out, ns, nd, epoch):
        g2, a, b, c, d, iters, epoch2 = U.insert_and_update(
            g, dl_in, dl_out, bl_in, bl_out, ns, nd, epoch,
            n_cap=n_cap, max_iters=max_iters)
        sat = U.saturated(iters, max_iters)
        return g2, a, b, c, d, Q.pack_labels(a, b, c, d), epoch2, sat

    in_sh = (sh.graph, plane, plane, plane, plane, repl, repl, repl)
    out_sh = (sh.graph, plane, plane, plane, plane,
              Q.PackedLabels(plane, plane, plane, plane), repl, repl)
    return jax.jit(impl, in_shardings=in_sh, out_shardings=out_sh)


def distributed_insert(idx: DBLIndex, mesh: Mesh, new_src, new_dst,
                       *, max_iters: int = 256, check: str = "warn"
                       ) -> DBLIndex:
    """Device-resident sharded insert: the old path ran the update
    unsharded and re-``device_put`` the whole index afterwards (a full host
    round-trip per batch); this threads ``index_shardings(mesh)`` through
    the jit boundary instead, so labels stay vertex-partitioned on device
    across insert batches.  ``check`` surfaces fixpoint saturation exactly
    like ``DBLIndex.insert_edges`` ("warn" default / "raise" / "defer" —
    defer skips the one-scalar host sync and only folds the flag into the
    index's sticky ``saturated`` field)."""
    if check not in ("warn", "raise", "defer"):
        raise ValueError(f"unknown check mode {check!r}")
    fn = _sharded_insert_fn(mesh, idx.n_cap, max_iters)
    ns = jnp.asarray(new_src, jnp.int32)
    nd = jnp.asarray(new_dst, jnp.int32)
    g2, a, b, c, d, packed, epoch2, sat = fn(
        idx.graph, idx.dl_in, idx.dl_out, idx.bl_in, idx.bl_out,
        ns, nd, jnp.asarray(idx.epoch, jnp.int32))
    il_kw = {}
    if idx.il_in is not None:
        # plug-in families ride the auto-partitioned path: inputs carry
        # their resident shardings and GSPMD propagates them
        il_in, il_out, it_il = U.insert_update_plugin(
            "il", g2, idx.il_in, idx.il_out, ns, nd,
            n_cap=idx.n_cap, max_iters=max_iters)
        il_kw = dict(il_in=il_in, il_out=il_out)
        sat = sat | U.saturated(it_il, max_iters)
    if check != "defer" and bool(np.asarray(sat)):
        if check == "raise":
            raise LabelSaturationError(_saturation_message(max_iters))
        warnings.warn(_saturation_message(max_iters),
                      LabelSaturationWarning, stacklevel=2)
    return idx._replace(
        graph=g2, dl_in=a, dl_out=b, bl_in=c, bl_out=d, packed=packed,
        epoch=epoch2, saturated=jnp.asarray(idx.saturated) | sat, **il_kw)


# ===================================================================
# Vertex-sharded lifecycle (all-gather-free; see core.planes)
# ===================================================================
def vertex_mesh(shards: int | None = None) -> Mesh:
    """A 1-axis ``"vertex"`` mesh over ``shards`` devices (default: all)."""
    from repro.launch.mesh import auto_mesh
    shards = shards or len(jax.devices())
    return auto_mesh((shards,), (PL.VERTEX_AXIS,))


def vertex_index_shardings(mesh: Mesh, *, il: bool = False) -> DBLIndex:
    """DBLIndex-shaped NamedShardings for the vertex-sharded layout: label
    planes (bool and packed) row-partitioned, the (n_cap,) leaf masks
    row-partitioned alongside them, everything else — graph, landmarks,
    epoch scalars — replicated (the graph is O(m) int32s, small next to
    the O(n·(k+k')) planes it indexes into).  ``il=True`` row-partitions
    the interval rank planes alongside the bool planes (same per-device
    byte scaling) and replicates the scalar seed."""
    from repro.launch.sharding import reach_vertex_shardings
    plane, vec, rep = reach_vertex_shardings(mesh)
    g = Graph(src=rep, dst=rep, n=rep, m=rep, del_at=rep, del_epoch=rep)
    packed = Q.PackedLabels(plane, plane, plane, plane)
    return DBLIndex(graph=g, landmarks=rep, dl_in=plane, dl_out=plane,
                    bl_in=plane, bl_out=plane, packed=packed,
                    bl_sources=vec, bl_sinks=vec, epoch=rep,
                    label_del_epoch=rep, saturated=rep,
                    il_in=plane if il else None,
                    il_out=plane if il else None,
                    il_seed=rep if il else None)


def place_vertex_sharded(idx: DBLIndex, mesh: Mesh) -> DBLIndex:
    """device_put every leaf into the vertex-sharded scheme."""
    PL._check_rows(idx.n_cap, PL.vertex_layout(mesh))
    sh = vertex_index_shardings(mesh, il=idx.il_in is not None)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), idx, sh)


def _check_saturation(sat, max_iters: int, check: str, stacklevel: int = 3):
    if check not in ("warn", "raise", "defer"):
        raise ValueError(f"unknown check mode {check!r}")
    if check != "defer" and bool(np.asarray(sat)):
        if check == "raise":
            raise LabelSaturationError(_saturation_message(max_iters))
        warnings.warn(_saturation_message(max_iters),
                      LabelSaturationWarning, stacklevel=stacklevel)


def _il_build_sharded(plan: PL.ShardPlan, sh: DBLIndex, n_cap: int,
                      dim: int, seed, live, max_iters: int,
                      halo_mode: str = "dense", telemetry=None,
                      halo_caps=None):
    """Sharded twin of ``interval.build_il``: the deterministic rank seed
    plane is row-placed and both directions run the MIN halo fixpoint from
    the all-ones frontier — the same rounds as the replicated min
    propagate, so the planes are bitwise identical."""
    fam = F.get("il")
    base = jax.device_put(fam.seed_plane(n_cap, dim, seed), sh.il_in)
    fr = jax.device_put(jnp.ones((n_cap,), jnp.bool_), sh.bl_sources)
    il_in, it0 = PL.halo_propagate(plan, base, fr, live, monoid="min",
                                   max_iters=max_iters, halo_mode=halo_mode,
                                   telemetry=telemetry,
                                   halo_caps=halo_caps)
    il_out, it1 = PL.halo_propagate(plan, base, fr, live, reverse=True,
                                    monoid="min", max_iters=max_iters,
                                    halo_mode=halo_mode, telemetry=telemetry,
                                    halo_caps=halo_caps)
    return il_in, il_out, jnp.stack([it0, it1])


def build_vertex_sharded(g: Graph, mesh: Mesh, *, n_cap: int, k: int = 64,
                         k_prime: int = 64, selection: str = "product",
                         leaf_r: int = 0, max_iters: int = 256,
                         check: str = "warn", plane_repr: str = "bool",
                         families=F.DEFAULT_FAMILIES,
                         il_dim: int = F.DEFAULT_IL_DIM, il_seed=0,
                         halo_mode: str = "dense", hub_count: int = 0,
                         telemetry=None, halo_caps=None
                         ) -> tuple[DBLIndex, PL.ShardPlan]:
    """Alg 1 with vertex-sharded label planes: ONE fused (k + k')-lane
    halo fixpoint per direction over row-partitioned seed planes.  Lanes
    are independent under the OR monoid, so the fused pass computes exactly
    the bits the four separate family fixpoints would — the labels are
    bitwise identical to ``DBLIndex.build``.  Returns (index, plan); the
    plan carries the edge partition + halo routing subsequent inserts,
    rebuilds, and sharded BFS residues reuse.

    ``families`` enables plug-in label families exactly as in
    ``DBLIndex.build``; the interval family's rank planes build through
    the MIN-monoid halo fixpoint, row-partitioned like the bool planes.

    ``halo_mode="sparse"`` runs every halo fixpoint through the compacted
    changed-row exchange (``core.halo``) — bitwise equal to dense;
    ``hub_count`` freezes that many top-cut-degree hub vertices on the
    plan for the sparse broadcast lane; ``telemetry`` (a
    ``halo.HaloTelemetry``) accumulates wire-byte/round accounting."""
    plugin_fams = F.plugins(families)
    layout = PL.vertex_layout(mesh)
    PL._check_rows(n_cap, layout)
    sh = vertex_index_shardings(mesh, il=bool(plugin_fams))
    g = jax.tree.map(lambda x, s: jax.device_put(x, s), g, sh.graph)
    landmarks = S.select_landmarks(g, n_cap=n_cap, k=k, method=selection)
    sources, sinks = S.leaf_masks(g, n_cap=n_cap, leaf_r=leaf_r)
    seeds = PL.PlaneStore.seeds(landmarks, sources, sinks, n_cap=n_cap,
                                k=k, k_prime=k_prime, layout=layout)
    fr_fwd, fr_bwd = seeds.seed_frontiers()
    plan = PL.shard_plan(g.src, g.dst, int(np.asarray(g.m)), n_cap, mesh,
                         hub_count=hub_count)
    live = G.edge_mask(g)
    x_fwd = jax.device_put(seeds.fused(), sh.dl_in)
    x_bwd = jax.device_put(seeds.fused(reverse=True), sh.dl_in)
    vec_sh = sh.bl_sources
    x_fwd, it0 = PL.halo_propagate(plan, x_fwd,
                                   jax.device_put(fr_fwd, vec_sh), live,
                                   max_iters=max_iters,
                                   plane_repr=plane_repr,
                                   halo_mode=halo_mode, telemetry=telemetry,
                                   halo_caps=halo_caps)
    x_bwd, it1 = PL.halo_propagate(plan, x_bwd,
                                   jax.device_put(fr_bwd, vec_sh), live,
                                   reverse=True, max_iters=max_iters,
                                   plane_repr=plane_repr,
                                   halo_mode=halo_mode, telemetry=telemetry,
                                   halo_caps=halo_caps)
    all_iters = [it0, it1]
    il_kw = {}
    for fam in plugin_fams:
        p_in, p_out, it_f = _il_build_sharded(plan, sh, n_cap, il_dim,
                                              il_seed, live, max_iters,
                                              halo_mode, telemetry,
                                              halo_caps)
        il_kw = dict(il_in=p_in, il_out=p_out,
                     il_seed=jnp.int32(il_seed))
        all_iters.append(it_f[0])
        all_iters.append(it_f[1])
    sat = U.saturated(jnp.stack(all_iters), max_iters)
    _check_saturation(sat, max_iters, check)
    store = seeds.with_fused(x_fwd, x_bwd)
    idx = DBLIndex(g, landmarks, store.dl_in, store.dl_out, store.bl_in,
                   store.bl_out, store.pack(), sources, sinks,
                   epoch=jnp.int32(0),
                   label_del_epoch=jnp.array(g.del_epoch, jnp.int32),
                   saturated=sat, **il_kw)
    return place_vertex_sharded(idx, mesh), plan


def insert_vertex_sharded(idx: DBLIndex, plan: PL.ShardPlan, new_src,
                          new_dst, *, max_iters: int = 256,
                          check: str = "warn", plane_repr: str = "bool",
                          extend: bool = True, halo_mode: str = "dense",
                          telemetry=None, halo_caps=None
                          ) -> tuple[DBLIndex, PL.ShardPlan, jax.Array]:
    """Batched Alg-3 insert on the vertex-sharded layout.

    The b inserted edges' seed rows cross shards once (psum of masked
    gathers, O(b·(k+k'))); the fixpoint then runs shard-local with
    per-round boundary-frontier halo exchange.  Labels come out bitwise
    equal to ``DBLIndex.insert_edges``.  Returns (index', plan',
    saturated_now) — the flag is returned rather than just folded in so
    serving engines can defer the host sync (``check="defer"``).

    The routing tables are EXTENDED in place of a from-scratch rebuild:
    ``planes.extend_plan`` appends the batch into the granule-rounded
    bucket tails in O(m + Δm log Δm) host work (no re-sort of existing
    edges), keeping compiled fixpoint shapes — and their executables —
    alive across steady insert streams.  ``extend=False`` forces the old
    O(m log m) from-scratch path (the bench differential); a plan that
    does not cover exactly the pre-insert edge prefix falls back to
    from-scratch with a warning rather than building wrong tables."""
    mesh = plan.mesh
    ns = jnp.asarray(np.asarray(new_src, np.int32))
    nd = jnp.asarray(np.asarray(new_dst, np.int32))
    m0 = int(np.asarray(idx.graph.m))
    g2 = G.insert_edges(idx.graph, ns, nd)
    if extend and plan.m == m0 and plan.n_cap == idx.n_cap:
        plan2 = PL.extend_plan(plan, np.asarray(ns), np.asarray(nd))
    else:
        if extend:
            warnings.warn(
                f"stale shard plan (covers m={plan.m}, n_cap={plan.n_cap}; "
                f"graph has m={m0}, n_cap={idx.n_cap}): rebuilding the "
                "routing tables from scratch", stacklevel=2)
        plan2 = PL.shard_plan(g2.src, g2.dst, int(np.asarray(g2.m)),
                              idx.n_cap, mesh,
                              edge_granule=plan.edge_granule,
                              halo_granule=plan.halo_granule,
                              hub_count=plan.hub_count)
    live = G.edge_mask(g2)
    store = idx.store
    seeded_f, fr_f = PL.sharded_seed_scatter(store.fused(), ns, nd,
                                             mesh=mesh)
    x_fwd, it0 = PL.halo_propagate(plan2, seeded_f, fr_f, live,
                                   max_iters=max_iters,
                                   plane_repr=plane_repr,
                                   halo_mode=halo_mode, telemetry=telemetry,
                                   halo_caps=halo_caps)
    seeded_b, fr_b = PL.sharded_seed_scatter(store.fused(reverse=True),
                                             nd, ns, mesh=mesh)
    x_bwd, it1 = PL.halo_propagate(plan2, seeded_b, fr_b, live,
                                   reverse=True, max_iters=max_iters,
                                   plane_repr=plane_repr,
                                   halo_mode=halo_mode, telemetry=telemetry,
                                   halo_caps=halo_caps)
    sat_now = U.saturated(jnp.stack([it0, it1]), max_iters)
    il_kw = {}
    if idx.il_in is not None:
        # MIN twin of the seeding above, mirroring the replicated
        # ``interval.insert_update_il`` role swap: edge (u, v) hands u's
        # ancestor mins to v and v's reach mins to u
        s_in, fr_i = PL.sharded_seed_scatter_min(idx.il_in, ns, nd,
                                                 mesh=mesh)
        il_in2, it2 = PL.halo_propagate(plan2, s_in, fr_i, live,
                                        monoid="min", max_iters=max_iters,
                                        halo_mode=halo_mode,
                                        telemetry=telemetry,
                                        halo_caps=halo_caps)
        s_out, fr_o = PL.sharded_seed_scatter_min(idx.il_out, nd, ns,
                                                  mesh=mesh)
        il_out2, it3 = PL.halo_propagate(plan2, s_out, fr_o, live,
                                         reverse=True, monoid="min",
                                         max_iters=max_iters,
                                         halo_mode=halo_mode,
                                         telemetry=telemetry,
                                         halo_caps=halo_caps)
        il_kw = dict(il_in=il_in2, il_out=il_out2)
        sat_now = sat_now | U.saturated(jnp.stack([it2, it3]), max_iters)
    _check_saturation(sat_now, max_iters, check)
    idx2 = idx.with_store(
        store.with_fused(x_fwd, x_bwd), graph=g2,
        epoch=jnp.asarray(idx.epoch, jnp.int32) + jnp.int32(1),
        saturated=jnp.asarray(idx.saturated) | sat_now, **il_kw)
    # normalize placements: re-packing and epoch arithmetic produce leaves
    # whose shardings the partitioner chose — pin them back to the scheme
    # so downstream executables see ONE sharding flavor per leaf (no jit
    # cache churn across insert batches; a no-op for already-placed leaves)
    return place_vertex_sharded(idx2, plan2.mesh), plan2, sat_now


def rebuild_vertex_sharded(idx: DBLIndex, plan: PL.ShardPlan | None, *,
                           mesh: Mesh | None = None, mode: str = "full",
                           selection: str = "product", leaf_r: int = 0,
                           max_iters: int = 256, compact: bool = True,
                           check: str = "warn",
                           delta_threshold: float = 0.99,
                           plane_repr: str = "bool",
                           halo_mode: str = "dense", telemetry=None,
                           halo_caps=None
                           ) -> tuple[DBLIndex, PL.ShardPlan, dict]:
    """Sharded twin of ``DBLIndex.rebuild_info``: full Alg-1 rebuild or the
    incremental delta repair, on row-partitioned planes.

    The delta plan (invalidation closure, seed churn, estimate) is computed
    by the same host-side ``DBLIndex._delta_plan``; the partial reset is
    the PlaneStore's row/column seed-reset (row-parallel, stays sharded);
    the repair fixpoint runs the halo exchange over the full live edge set
    (the replicated path's dirty-region edge subset is a dispatch-size
    optimization — relaxing the extra edges into clean rows is a no-op, so
    labels remain bitwise equal to a full rebuild).  Returns
    (index', plan', info)."""
    mesh = mesh or (plan.mesh if plan is not None else None)
    if mesh is None:
        raise ValueError("rebuild_vertex_sharded needs a plan or a mesh")
    if mode not in ("full", "delta", "auto"):
        raise ValueError(f"unknown rebuild mode {mode!r}")
    n_cap, k, kp = idx.n_cap, idx.k, idx.k_prime
    build_kw = dict(n_cap=n_cap, k=k, k_prime=kp, selection=selection,
                    leaf_r=leaf_r, max_iters=max_iters, check=check,
                    plane_repr=plane_repr, halo_mode=halo_mode,
                    telemetry=telemetry, halo_caps=halo_caps,
                    hub_count=plan.hub_count if plan is not None else 0)
    if idx.il_in is not None:
        build_kw.update(families=idx.families, il_dim=idx.il_dim,
                        il_seed=idx.il_seed)

    def full(reason):
        g2 = G.compact(idx.graph) if compact else idx.graph
        idx2, plan2 = build_vertex_sharded(g2, mesh, **build_kw)
        idx2 = idx2._replace(
            epoch=jnp.asarray(idx.epoch, jnp.int32) + jnp.int32(1))
        return idx2, plan2, {"mode": "full", "reason": reason}

    if mode == "full":
        return full("forced")
    if bool(np.asarray(idx.saturated)):
        return full("saturated")
    dplan = idx._delta_plan(selection=selection, leaf_r=leaf_r)
    est = dplan["estimate"]
    if mode == "auto" and est["frac"] > delta_threshold:
        i2, p2, info = full("estimate")
        return i2, p2, {**info, "estimate": est}
    g = idx.graph
    m_now = int(np.asarray(g.m))
    gran = {} if plan is None else dict(edge_granule=plan.edge_granule,
                                        halo_granule=plan.halo_granule,
                                        hub_count=plan.hub_count)
    if plan is None or plan.n_cap != n_cap or plan.mesh != mesh \
            or plan.m > m_now:
        plan = PL.shard_plan(g.src, g.dst, m_now, n_cap, mesh, **gran)
    elif plan.m < m_now:
        # O(Δm) catch-up over the append-only window the plan missed —
        # slots [plan.m, m_now) are exactly the edges inserted since the
        # plan was built.  The window may span SEVERAL insert batches with
        # deletes interleaved, so keep every raw slot (dedupe=False): the
        # per-batch first-occurrence dedupe would keep a tombstoned slot
        # and drop its live re-inserted twin, and the live edge would
        # never relax.  Raw slots make the bucket arrays bit-identical to
        # the from-scratch tables (duplicates/self-loops are as harmless
        # here as they are in _build_dir).
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        plan = PL.extend_plan(plan, src[plan.m:m_now], dst[plan.m:m_now],
                              dedupe=False)
    (x_fwd, x_bwd, fresh_fwd, fresh_bwd, seed_fwd, seed_bwd,
     fr_fwd, fr_bwd) = L.delta_plane_state(
        g, idx.dl_in, idx.dl_out, idx.bl_in, idx.bl_out,
        idx.landmarks, dplan["landmarks"], idx.bl_sources, idx.bl_sinks,
        dplan["sources"], dplan["sinks"],
        dplan["dirty_fwd_j"], dplan["dirty_bwd_j"],
        n_cap=n_cap, k=k, k_prime=kp)
    live = G.edge_mask(g)
    iters = []
    sh = vertex_index_shardings(mesh, il=idx.il_in is not None)
    for rev, x, seed, fresh, fr in ((False, x_fwd, seed_fwd, fresh_fwd,
                                     fr_fwd),
                                    (True, x_bwd, seed_bwd, fresh_bwd,
                                     fr_bwd)):
        fr = fr | (seed & fresh[None, :]).any(axis=1)
        x, it = PL.halo_propagate(plan, jax.device_put(x, sh.dl_in),
                                  jax.device_put(fr, sh.bl_sources), live,
                                  reverse=rev, max_iters=max_iters,
                                  plane_repr=plane_repr,
                                  halo_mode=halo_mode, telemetry=telemetry,
                                  halo_caps=halo_caps)
        iters.append(it)
        if rev:
            x_bwd = x
        else:
            x_fwd = x
    g2 = G.compact(g) if compact else g
    plan2 = PL.shard_plan(g2.src, g2.dst, int(np.asarray(g2.m)), n_cap,
                          mesh, **gran) if compact else plan
    # plug-in family repair, as in the replicated delta path: every
    # interval dimension is churned under deletion, so both planes are
    # re-derived from the stored seed over the live edge set — bitwise
    # equal to a full rebuild (deterministic in (seed, n_cap, dim))
    il_kw = {}
    if idx.il_in is not None:
        p_in, p_out, it_f = _il_build_sharded(
            plan2, sh, n_cap, idx.il_dim, idx.il_seed,
            G.edge_mask(g2), max_iters, halo_mode, telemetry,
            halo_caps)
        il_kw = dict(il_in=p_in, il_out=p_out)
        iters.append(it_f[0])
        iters.append(it_f[1])
    sat = U.saturated(jnp.stack(iters), max_iters)
    _check_saturation(sat, max_iters, check)
    store = idx.store.with_fused(x_fwd, x_bwd,
                                 landmarks=dplan["landmarks"],
                                 bl_sources=dplan["sources"],
                                 bl_sinks=dplan["sinks"])
    idx2 = idx.with_store(
        store, graph=g2,
        epoch=jnp.asarray(idx.epoch, jnp.int32) + jnp.int32(1),
        label_del_epoch=jnp.array(g2.del_epoch, jnp.int32),
        saturated=sat, **il_kw)
    idx2 = place_vertex_sharded(idx2, mesh)
    reason = "forced" if mode == "delta" else "estimate"
    return idx2, plan2, {"mode": "delta", "reason": reason,
                         "estimate": est}

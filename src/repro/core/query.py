"""Query processing (paper Algorithm 2), batched.

Phase 1 — label verdicts over packed words (the ρ > 95% fast path):
  +1  reachable    (Lemma 1:   DL_out(u) ∩ DL_in(v) ≠ ∅, or u == v)
   0  unreachable  (Lemma 2:   BL containment violated;
                    Theorem 1: DL says v→u but not u→v;
                    Theorem 2: u or v is landmark-covered and DL said no)
  -1  unknown      → phase 2.

Phase 2 — batched pruned BFS: Alg 2 lines 14-24 with the two per-vertex
pruning tests (lines 20/22) hoisted into a per-query *admit plane*, legal
because labels are read-only during query processing.  Queries run as lanes
of a (n_cap, Q_chunk) frontier plane.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bitset
from .graph import Graph, edge_mask
from .interval import il_negative
from .pull import tail_live


class PackedLabels(NamedTuple):
    dl_in: jax.Array   # (n_cap, Wk)  uint32
    dl_out: jax.Array  # (n_cap, Wk)  uint32
    bl_in: jax.Array   # (n_cap, Wk') uint32
    bl_out: jax.Array  # (n_cap, Wk') uint32


def pack_labels(dl_in, dl_out, bl_in, bl_out) -> PackedLabels:
    return PackedLabels(bitset.pack(dl_in), bitset.pack(dl_out),
                        bitset.pack(bl_in), bitset.pack(bl_out))


class RowBlocks(NamedTuple):
    """The eight gathered label rows every Alg-2 verdict rule reads.

    Verdicts are a pure function of these (Q, W) row blocks — NOT of the
    full (n_cap, W) planes — which is what makes the vertex-sharded verdict
    path all-gather-free: each shard contributes the rows it owns (zeros
    elsewhere) and one ``psum`` reconstructs the blocks on every device
    (O(Q·W) traffic, never O(n_cap·W); see ``core.planes.sharded_rows``).
    """
    dlo_u: jax.Array   # DL_out[u]  (Q, Wk)
    dli_v: jax.Array   # DL_in[v]
    dlo_v: jax.Array   # DL_out[v]
    dli_u: jax.Array   # DL_in[u]
    blin_u: jax.Array  # BL_in[u]   (Q, Wk')
    blin_v: jax.Array  # BL_in[v]
    blout_v: jax.Array  # BL_out[v]
    blout_u: jax.Array  # BL_out[u]


def gather_rows(p: PackedLabels, u: jax.Array, v: jax.Array) -> RowBlocks:
    """Local (replicated-layout) row gather behind every verdict rule."""
    return RowBlocks(p.dl_out[u], p.dl_in[v], p.dl_out[v], p.dl_in[u],
                     p.bl_in[u], p.bl_in[v], p.bl_out[v], p.bl_out[u])


def gather_il_rows(il, u: jax.Array, v: jax.Array):
    """The four (Q, 2*dim) interval rows the "il" plug-in family's
    containment prune reads (``None`` in → ``None`` out): the row-block
    discipline of :class:`RowBlocks` extended to the registry's first
    negative-prune plug-in, so the vertex-sharded path can psum-reconstruct
    these alongside the eight core rows (``core.planes.sharded_il_rows``).

    ``il`` is the index's ``(il_in, il_out)`` operand pytree."""
    if il is None:
        return None
    il_in, il_out = il
    return (il_out[u], il_out[v], il_in[u], il_in[v])


def verdict_parts_rows(r: RowBlocks):
    """(pos_lbl, bl_neg, thm) boolean evidence masks behind the four rules,
    computed from gathered row blocks.

    Kept separate because the rules degrade differently when the index is
    *dirty* (tombstoned deletions not yet rebuilt into labels):

    - ``pos_lbl`` (Lemma 1) and ``thm`` (Theorems 1/2) are built on POSITIVE
      label evidence ("a landmark path exists") — under deletions the labels
      over-approximate reachability, so this evidence can be stale and the
      verdicts it feeds must downgrade to unknown;
    - ``bl_neg`` (Lemma 2) only needs label *completeness* (every true fact
      has its bit).  Bits are never removed, so BL containment violations
      stay sound proofs of unreachability under any number of deletions.
    """
    pos_lbl = bitset.intersect_any(r.dlo_u, r.dli_v)
    bl_neg = (~bitset.subset(r.blin_u, r.blin_v)
              | ~bitset.subset(r.blout_v, r.blout_u))
    thm = (bitset.intersect_any(r.dlo_v, r.dli_u)
           | bitset.intersect_any(r.dlo_u, r.dli_u)
           | bitset.intersect_any(r.dlo_v, r.dli_v))
    return pos_lbl, bl_neg, thm


def _verdict_parts(p: PackedLabels, u: jax.Array, v: jax.Array):
    return verdict_parts_rows(gather_rows(p, u, v))


@jax.jit
def label_verdicts(p: PackedLabels, u: jax.Array, v: jax.Array,
                   il=None) -> jax.Array:
    """(Q,) int8 verdicts from labels only (Alg 2 lines 6-13).

    ``il`` is the optional ``(il_in, il_out)`` interval-family operand: its
    containment violations join the negative rules (a plug-in negative
    prune, same soundness slot as Lemma 2).  ``None`` (the fused-core
    default) traces the exact pre-registry program — no leaves, no
    operands, bitwise-identical verdicts."""
    pos_lbl, bl_neg, thm = _verdict_parts(p, u, v)
    if il is not None:
        bl_neg = bl_neg | il_negative(*gather_il_rows(il, u, v))
    pos = pos_lbl | (u == v)
    neg = ~pos & (bl_neg | thm)
    return jnp.where(pos, jnp.int8(1), jnp.where(neg, jnp.int8(0), jnp.int8(-1)))


@jax.jit
def dirty_label_verdicts(p: PackedLabels, u: jax.Array, v: jax.Array
                         ) -> jax.Array:
    """(Q,) int8 verdicts SOUND FOR A DIRTY INDEX (pending deletions).

    Only the deletion-monotone rules survive: self-queries stay +1 and BL
    containment violations stay 0; everything else is unknown and rides the
    live-edge BFS.  This is the verdict-downgrade half of fully-dynamic DBL.
    """
    _, bl_neg, _ = _verdict_parts(p, u, v)
    same = u == v
    return jnp.where(same, jnp.int8(1),
                     jnp.where(bl_neg, jnp.int8(0), jnp.int8(-1)))


def cut_verdicts(p: PackedLabels, u: jax.Array, v: jax.Array,
                 m_cut: jax.Array, m_total: jax.Array,
                 d_fresh: jax.Array | bool, il=None) -> jax.Array:
    """(Q,) int8 verdicts with BOTH staleness cutoffs applied — the traceable
    jnp twin of the ``dbl_query`` kernel's cutoff path:

    - per-lane *edge-count* cutoff (insert staleness): label positives on
      lanes with ``m_cut < m_total`` degrade to unknown (``asof_verdicts``);
    - *tombstone* cutoff (deletion staleness): when ``d_fresh`` is False the
      labels carry deletions not yet rebuilt, so positives AND theorem-1/2
      negatives degrade — only self-queries and BL negatives survive.

    ``d_fresh`` broadcasts: a scalar (whole dispatch clean/dirty) or (Q,).
    ``il`` is the optional ``(il_in, il_out)`` interval operand.
    """
    return cut_verdicts_rows(gather_rows(p, u, v), u, v, m_cut, m_total,
                             d_fresh, il_rows=gather_il_rows(il, u, v))


def cut_verdicts_rows(r: RowBlocks, u: jax.Array, v: jax.Array,
                      m_cut: jax.Array, m_total: jax.Array,
                      d_fresh: jax.Array | bool,
                      il_rows=None) -> jax.Array:
    """``cut_verdicts`` from pre-gathered row blocks — the entry point the
    vertex-sharded engine uses after its psum row reconstruction (the rows,
    not the planes, cross shards).

    ``il_rows`` is ``gather_il_rows``' 4-tuple (or None).  The interval
    prune is *insert-monotone* (intervals only coarsen under insertions, so
    a violation at newer planes holds at every older snapshot — the BL
    argument, no ``m_cut`` gate) but NOT tombstone-sound: while the labels
    are deletion-stale (``d_fresh`` False) the family contributes nothing,
    exactly like the DL positives — its term only joins the fresh branch.
    """
    pos_lbl, bl_neg, thm = verdict_parts_rows(r)
    same = u == v
    d_fresh = jnp.asarray(d_fresh, jnp.bool_)
    m_fresh = m_cut >= m_total
    pos0 = pos_lbl | same
    neg_lbl = bl_neg if il_rows is None else bl_neg | il_negative(*il_rows)
    neg0 = ~pos0 & (neg_lbl | thm)
    pos = (pos_lbl & m_fresh & d_fresh) | same
    neg = jnp.where(d_fresh, neg0, ~same & bl_neg)
    return jnp.where(pos, jnp.int8(1), jnp.where(neg, jnp.int8(0), jnp.int8(-1)))


def verdict_counts(verd: jax.Array, r: RowBlocks,
                   il_rows=None) -> jax.Array:
    """(4,) int32 per-family prune attribution [dl⁺, bl⁻, il⁻, thm⁻] for one
    verdict batch — the label-phase half of ``EngineStats.prune_hits``.

    Each resolved lane is charged to exactly one family, in the order the
    fused verdict evaluates its evidence: positives to DL (self-query pad
    lanes are the caller's to subtract — the engine knows its pad count),
    negatives to BL containment first, then the interval containment, then
    the theorem-1/2 rules.  Unknown lanes are counted by the caller when
    they resolve through the BFS residue."""
    _, bl_neg, _ = verdict_parts_rows(r)
    if il_rows is None:
        il_neg = jnp.zeros_like(bl_neg)
    else:
        il_neg = il_negative(*il_rows)
    neg = verd == jnp.int8(0)
    return jnp.stack([
        jnp.sum(verd == jnp.int8(1)),
        jnp.sum(neg & bl_neg),
        jnp.sum(neg & ~bl_neg & il_neg),
        jnp.sum(neg & ~bl_neg & ~il_neg),
    ]).astype(jnp.int32)


#: per-lane edge-count-cutoff sentinel that is >= any reachable edge count,
#: marking a lane (or a padding lane) as always-fresh: full DL prune, every
#: live edge visible.  All cutoff consumers (QueryEngine, both kernel ops
#: wrappers) must share this value — a lane padded with anything smaller
#: would silently flip to the stale path.
FRESH_CUT = 2**31 - 1


def asof_verdicts(verd: jax.Array, u: jax.Array, v: jax.Array,
                  m_cut: jax.Array, m_total: jax.Array) -> jax.Array:
    """Downgrade verdicts computed from *newer* labels to be valid "as of"
    a per-lane edge-count cutoff (insert-only monotonicity, both ways):

    - ``0`` stays ``0``: unreachable under a superset edge set is
      unreachable under every older subset — stale negatives are free;
    - ``+1`` survives only for fresh lanes (``m_cut >= m_total``) or
      self-queries: a positive proven by newer labels may ride edges the
      lane's snapshot did not have, so it degrades to ``-1`` (unknown) and
      the lane rides the cutoff BFS instead.

    This is the label-side half of cross-snapshot coalescing: one verdict
    dispatch against the newest labels serves lanes from every epoch.
    """
    fresh = m_cut >= m_total
    stale_pos = (verd == jnp.int8(1)) & ~fresh & (u != v)
    return jnp.where(stale_pos, jnp.int8(-1), verd.astype(jnp.int8))


@jax.jit
def label_stats(p: PackedLabels, u: jax.Array, v: jax.Array) -> dict:
    """Per-mechanism answer masks (paper Table 4 columns)."""
    dlo_u, dli_v = p.dl_out[u], p.dl_in[v]
    dlo_v, dli_u = p.dl_out[v], p.dl_in[u]
    pos = bitset.intersect_any(dlo_u, dli_v) | (u == v)
    thm1 = ~pos & bitset.intersect_any(dlo_v, dli_u)
    thm2 = ~pos & (bitset.intersect_any(dlo_u, dli_u)
                   | bitset.intersect_any(dlo_v, dli_v))
    bl_neg = (~bitset.subset(p.bl_in[u], p.bl_in[v])
              | ~bitset.subset(p.bl_out[v], p.bl_out[u]))
    dl_only = pos | thm1 | thm2
    bl_only = bl_neg
    return {"dl": dl_only, "bl": ~pos & bl_only, "dbl": dl_only | (~pos & bl_neg)}


def _admit_plane(p: PackedLabels, u: jax.Array, v: jax.Array,
                 n_cap: int, dl_on: jax.Array | None = None,
                 il=None, il_on: jax.Array | None = None) -> jax.Array:
    """(n_cap, Qc) bool — vertices x admissible in query q's BFS.

    admit = BL_Contain(x, v_q) ∧ ¬DL_Intersec(u_q, x)   (Alg 2 lines 20/22),
    further ∧ ¬IL_Violate(x, v_q) when the interval family is enabled.

    ``dl_on`` (Qc,) bool gates the DL-intersection prune per lane.  The BL
    containment prune is *monotone-safe*: labels only gain bits under
    insert-only updates, so containment at a newer snapshot is implied by any
    path that existed at an older one — pruning an epoch-stale lane's BFS
    with newer BL labels never cuts a true old-snapshot path.  The DL prune
    is not (its soundness argument runs through the lane's verdict being
    non-positive *at the label snapshot*), so epoch-stale lanes disable it.

    ``il`` (il_in, il_out) adds the interval containment test per vertex:
    x on a live path to v_q implies interval containment, so a violation
    prunes x from lane q.  Like BL it is insert-monotone (no per-lane
    epoch gate), but it is NOT deletion-sound, so ``il_on`` (scalar or
    (Qc,)) gates it off for tombstone-dirty dispatches.
    """
    c1 = bitset.subset(p.bl_in[:, None, :], p.bl_in[v][None, :, :])
    c2 = bitset.subset(p.bl_out[v][None, :, :], p.bl_out[:, None, :])
    d = bitset.intersect_any(p.dl_out[u][None, :, :], p.dl_in[:, None, :])
    if dl_on is not None:
        d = d & dl_on[None, :]
    admit = c1 & c2 & ~d
    if il is not None:
        il_in, il_out = il
        bad = (jnp.any(il_out[:, None, :] > il_out[v][None, :, :], axis=-1)
               | jnp.any(il_in[v][None, :, :] > il_in[:, None, :], axis=-1))
        if il_on is not None:
            bad = bad & jnp.broadcast_to(il_on, bad.shape[-1:])[None, :]
        admit = admit & ~bad
    return admit


#: dtypes selectable for the BFS frontier planes (``pruned_bfs`` and the
#: sharded twin in ``core.planes``).  "int8" and "int32" are lane-wise:
#: they name the dtype of the push form's (m_cap, Qc) segment-max operand,
#: "int8" (the default) at 1 byte a lane against int32's 4.  That operand
#: exists only on the push paths: a foreign snapshot, ``query()``'s host
#: reference, the sharded twin, and the pull form's insert tail.  A bound
#: replicated engine pulls (``pulled_bfs``) on bool planes with no such
#: operand, so there the two choose only the tail's dtype.  "packed" packs
#: the query-lane axis into uint32 words (32 lanes/word) and the whole BFS
#: — frontier, visited, admit, per-lane cutoffs, hits — runs on word planes
#: via the bitset segment-OR algebra (replicated ``pruned_bfs`` only; the
#: sharded twin rejects it; no pull form).  All flavors produce
#: bitwise-identical hits (parity-swept in tests/test_kernels.py).
FRONTIER_DTYPES = {"int8": jnp.int8, "int32": jnp.int32,
                   "packed": jnp.uint32}


def _pruned_bfs_packed(g, p, u, v, admit, m_cut, dl_on, il=None, il_on=None,
                       *, n_cap, max_iters, counted=False):
    """Word-packed BFS lanes: (n_cap, Wq) uint32 planes, Wq = ceil(Qc/32).

    Identical round structure to the lane-wise loop — gather frontier words
    along live (and per-lane cut-admitted) edges, segment-OR by dst, gate by
    admit/visited/hit — so the frontier evolution, termination, and hits are
    bitwise equal.  The per-edge cutoff mask packs ONCE per dispatch (it is
    loop-invariant), and the dst-argsort is hoisted out of the loop."""
    qc = u.shape[0]
    lane_mask = bitset.pad_mask(qc)                    # (Wq,)
    live = edge_mask(g)
    if admit is None:
        admit = _admit_plane(p, u, v, n_cap, dl_on, il, il_on)
    elif admit.dtype != jnp.bool_:
        admit = admit > 0
    admit_w = bitset.pack(admit)                       # (n_cap, Wq)
    order = jnp.argsort(g.dst)
    src_s, dst_s, live_s = g.src[order], g.dst[order], live[order]
    if m_cut is not None:
        eids = jnp.arange(g.src.shape[0], dtype=jnp.int32)
        cut_ws = bitset.pack(eids[order][:, None] < m_cut[None, :])
    ids = jnp.arange(n_cap, dtype=jnp.int32)
    frontier_w = bitset.pack(ids[:, None] == u[None, :])
    visited_w = frontier_w
    hit_w = jnp.zeros(lane_mask.shape, jnp.uint32)
    lanes = jnp.arange(qc)
    lw = lanes // 32
    lb = (lanes % 32).astype(jnp.uint32)

    def cond(state):
        fw, _, hw, it = state[:4]
        done = jnp.all((hw & lane_mask) == lane_mask)
        return jnp.logical_and(jnp.any(fw != 0),
                               jnp.logical_and(~done, it < max_iters))

    def body(state):
        fw, vw, hw, it = state[:4]
        with jax.named_scope("bfs.gather"):
            contrib = jnp.where(live_s[:, None], fw[src_s], jnp.uint32(0))
            if m_cut is not None:
                contrib &= cut_ws
        with jax.named_scope("bfs.reduce"):
            nw = bitset.sorted_segment_or(contrib, dst_s, n_cap)
        with jax.named_scope("bfs.update"):
            nw = nw & admit_w & ~vw & ~hw[None, :]
            rows = nw[v]                               # (qc, Wq)
            hits = ((rows[lanes, lw] >> lb) & jnp.uint32(1)).astype(jnp.bool_)
            hw = hw | bitset.pack(hits)
            vw = vw | nw
            if counted:
                # hits are new: nw was masked by the lanes already hit
                return nw, vw, hw, it + 1, jnp.where(hits, it + 1, state[4])
        return nw, vw, hw, it + 1

    state = (frontier_w, visited_w, hit_w, jnp.int32(0))
    if counted:
        state += (jnp.zeros((qc,), jnp.int32),)
    state = jax.lax.while_loop(cond, body, state)
    if counted:
        return level_table(state[4], state[3])
    return bitset.unpack(state[2], qc)


def pulled_bfs(g, p, u, v, admit, m_cut, dl_on, layout, il=None, il_on=None,
               *, n_cap, max_iters, ftype=jnp.int8, counted=False):
    """The lane-wise BFS of ``pruned_bfs`` in pull form, over a
    ``core.pull.InEdgeLayout`` of ``g``: each level gathers, per in-degree
    class, the frontier rows of every row's in-edge sources and ORs them
    across the row's width — no scatter and no sort in the level loop.

    The loop runs in the layout's row order.  Once per call, outside it
    (``bfs.layout``): the admit plane is permuted into row order, the
    lanes' endpoints are mapped through ``pos`` (dead lanes' sources stay
    out of range), and, if the graph was tombstoned since the layout was
    built, slots of dead edges are pointed at the layout's empty row.
    The slots of ``layout.tail`` (edges inserted since the layout, and
    any a re-layout left without a row) are relaxed in the push form under
    ``bfs.tail``.  Frontiers, hits, level counts and
    the per-lane ``m_cut`` cutoffs are those of the push form, bit for
    bit; ``ftype`` is the tail's segment-reduce dtype."""
    qc = u.shape[0]
    rows = layout.inv.shape[0]
    zero_row = jnp.int32(rows - 1)
    m_cap = g.src.shape[0]
    with jax.named_scope("bfs.layout"):
        if admit is None:
            admit = _admit_plane(p, u, v, n_cap, dl_on, il, il_on)
        elif admit.dtype != jnp.bool_:
            admit = admit > 0
        admit = admit[jnp.minimum(layout.inv, n_cap - 1)]      # (R, Qc)
        pu = jnp.where((u >= 0) & (u < n_cap),
                       layout.pos[jnp.clip(u, 0, n_cap - 1)], rows)
        pv = layout.pos[v]

        def drop_dead(srcs):
            return tuple(
                jnp.where(g.del_at[jnp.minimum(e, m_cap - 1)] > g.del_epoch,
                          s, zero_row)
                for s, e in zip(srcs, layout.eid))
        srcs = jax.lax.cond(g.del_epoch > layout.del_epoch, drop_dead,
                            lambda s: s, layout.src)
    n_pulled = sum(s.shape[1] for s in srcs)
    n_tail = layout.tail.shape[0]
    if n_tail:
        with jax.named_scope("bfs.tail"):
            te = layout.tail
            tc = jnp.minimum(te, m_cap - 1)
            t_live = tail_live(g, te)
            t_src = layout.pos[g.src[tc]]
            t_dst = layout.pos[g.dst[tc]]
    frontier = jnp.arange(rows, dtype=jnp.int32)[:, None] == pu[None, :]
    visited = frontier
    hit = jnp.zeros((qc,), jnp.bool_)
    lanes = jnp.arange(qc)

    def cond(state):
        frontier, _, hit, it = state[:4]
        return jnp.logical_and(frontier.any(),
                               jnp.logical_and(~hit.all(), it < max_iters))

    def body(state):
        frontier, visited, hit, it = state[:4]
        parts = []
        for s, e in zip(srcs, layout.eid):
            with jax.named_scope("bfs.gather"):
                rows_in = frontier[s]                   # (width, rows_c, Qc)
                if m_cut is not None:
                    rows_in &= e[:, :, None] < m_cut[None, None, :]
            with jax.named_scope("bfs.reduce"):
                parts.append(jnp.any(rows_in, axis=0))
        with jax.named_scope("bfs.reduce"):
            parts.append(jnp.zeros((rows - n_pulled, qc), jnp.bool_))
            nxt = jnp.concatenate(parts, axis=0)
        if n_tail:
            with jax.named_scope("bfs.tail"):
                contrib = frontier[t_src] & t_live[:, None]
                if m_cut is not None:
                    contrib &= te[:, None] < m_cut[None, :]
                nxt |= jax.ops.segment_max(contrib.astype(ftype), t_dst,
                                           num_segments=rows) > 0
        with jax.named_scope("bfs.update"):
            nxt = nxt & admit & ~visited & ~hit[None, :]
            new_hit = nxt[pv, lanes]      # new: nxt was masked by ~hit
            hit = hit | new_hit
            visited = visited | nxt
            if counted:
                return (nxt, visited, hit, it + 1,
                        jnp.where(new_hit, it + 1, state[4]))
        return nxt, visited, hit, it + 1

    state = (frontier, visited, hit, jnp.int32(0))
    if counted:
        state += (jnp.zeros((qc,), jnp.int32),)
    state = jax.lax.while_loop(cond, body, state)
    if counted:
        return level_table(state[4], state[3])
    return state[2]


def level_table(hit_level: jax.Array, levels: jax.Array) -> jax.Array:
    """(Qc, 2) int32 — per lane, the level at which it hit (0: never) and
    the level at which it was answered: its hit level, else ``levels``,
    the loop's count at exit (an unreachable lane is answered only when
    the loop ends).  A row per lane, like the hits it stands for: the
    answer columns are what the open-lane counters sum, and the loop's
    level count is their largest entry."""
    return jnp.stack([hit_level, jnp.where(hit_level > 0, hit_level, levels)],
                     axis=1)


def split_counted(table: jax.Array):
    """``(hit, hit_level, levels)`` from ``pruned_bfs(..., counted=True)``'s
    (Qc, 2) level table.  ``levels`` is the largest answer level: the loop
    ends at its last lane's hit, or with lanes that never hit, which are
    answered at its last level."""
    hit_level = table[:, 0]
    return hit_level > 0, hit_level, jnp.max(table[:, 1])


@functools.partial(jax.jit, static_argnames=("n_cap", "max_iters",
                                             "frontier_dtype", "counted"))
def pruned_bfs(g: Graph, p: PackedLabels, u: jax.Array, v: jax.Array,
               admit: jax.Array | None = None,
               m_cut: jax.Array | None = None,
               dl_clean: jax.Array | None = None,
               il=None,
               *, n_cap: int, max_iters: int = 256,
               frontier_dtype: str = "int8",
               counted: bool = False, layout=None) -> jax.Array:
    """(Qc,) bool — resolve unknown queries by label-pruned BFS lanes.

    ``admit`` lets callers supply a precomputed (n_cap, Qc) admit plane
    (e.g. from the bfs_prune Pallas kernel); default is the jnp plane.

    ``m_cut`` (Qc,) int32 is a per-lane *edge-count cutoff*: lane q only
    traverses edges with append index < m_cut[q].  Because the edge arrays
    are append-only, edge index < m-at-epoch-e is exactly the edge set the
    graph had at snapshot epoch e — so a cutoff BFS answers lane q "as of"
    its submit epoch even though it runs on the newest arrays, which is what
    lets the QueryEngine coalesce residues across snapshots into one
    dispatch.  Lanes with m_cut >= g.m see every live edge and keep the DL
    prune; stale lanes drop it (see ``_admit_plane``).

    ``dl_clean`` (() bool, default True) gates the DL prune on the LABELS
    being deletion-clean: when the graph carries tombstones the labels have
    not been rebuilt for, the DL-intersection evidence the prune rests on
    may be stale, so a dirty dispatch drops it for every lane.  The BL
    containment prunes stay on — along any live path x -> ... -> v the
    edge-wise label-coherence invariant (maintained by build, kept by
    deletes which only remove constraints, and restored by every insert
    fixpoint) guarantees BL(x) ⊆ BL(v), so the containment test never cuts
    a live path even under tombstones.  Tombstoned edges are excluded from
    traversal automatically via ``edge_mask``.

    ``frontier_dtype`` ("int8" default / "int32" / "packed") picks the
    element type the (m_cap, Qc) relaxation operand is segment-reduced in —
    the narrow plane cuts the reduction bytes 4x with bitwise-identical hits
    (the planes only ever carry 0/1; empty segments come back at the dtype's
    minimum, so the frontier re-binarizes with ``> 0`` rather than a cast).
    "packed" packs the lane axis into uint32 words and runs the whole loop
    on (n_cap, ceil(Qc/32)) word planes — 32 lanes per gather/reduce element.

    ``il`` (il_in, il_out) threads the interval family's containment prune
    into the admit plane.  It is insert-monotone like BL (no per-lane
    ``m_cut`` gate) but not deletion-sound, so it shares the ``dl_clean``
    tombstone gate — a dirty dispatch drops it for every lane.

    ``counted=True`` also counts levels (one level = one relaxation round
    of the loop) and returns, in place of the hits, the (Qc, 2) int32
    ``level_table``: row q is lane q's (hit level, answer level), nonzero
    in its first entry exactly when lane q hit.  ``split_counted`` turns
    it into ``(hit, hit_level, levels)``; the hits are the same bits.

    ``layout`` (a ``core.pull.InEdgeLayout`` of ``g``) runs the same BFS
    in pull form (``pulled_bfs``): same answers and level table, with no
    scatter in the level loop.  Not with ``frontier_dtype="packed"``.
    """
    ftype = FRONTIER_DTYPES[frontier_dtype]
    qc = u.shape[0]
    live = edge_mask(g)
    clean = jnp.asarray(True if dl_clean is None else dl_clean, jnp.bool_)
    if m_cut is None:
        dl_on = None if dl_clean is None else jnp.broadcast_to(clean, u.shape)
    else:
        eids = jnp.arange(g.src.shape[0], dtype=jnp.int32)
        dl_on = (m_cut >= g.m) & clean
    il_on = None if (il is None or dl_clean is None) \
        else jnp.broadcast_to(clean, u.shape)
    if layout is not None:
        if frontier_dtype == "packed":
            raise ValueError("the pull form runs lane-wise frontier planes; "
                             "frontier_dtype='packed' has no layout path")
        return pulled_bfs(g, p, u, v, admit, m_cut, dl_on, layout, il, il_on,
                          n_cap=n_cap, max_iters=max_iters, ftype=ftype,
                          counted=counted)
    if frontier_dtype == "packed":
        return _pruned_bfs_packed(g, p, u, v, admit, m_cut, dl_on, il, il_on,
                                  n_cap=n_cap, max_iters=max_iters,
                                  counted=counted)
    if admit is None:
        admit = _admit_plane(p, u, v, n_cap, dl_on, il, il_on)  # (n_cap, Qc)
    elif admit.dtype != jnp.bool_:
        # kernel-supplied admit planes may arrive int8 (same narrow-plane
        # rationale); re-binarize once before the loop
        admit = admit > 0
    ids = jnp.arange(n_cap, dtype=jnp.int32)
    frontier = ids[:, None] == u[None, :]          # (n_cap, Qc)
    visited = frontier
    hit = jnp.zeros((qc,), jnp.bool_)
    lanes = jnp.arange(qc)

    def cond(state):
        frontier, _, hit, it = state[:4]
        return jnp.logical_and(frontier.any(),
                               jnp.logical_and(~hit.all(), it < max_iters))

    def body(state):
        frontier, visited, hit, it = state[:4]
        with jax.named_scope("bfs.gather"):
            contrib = frontier[g.src] & live[:, None]
            if m_cut is not None:
                # fused into the contrib elementwise op each iteration — no
                # persistent (m_cap, Qc) mask carried across the while-loop
                contrib &= eids[:, None] < m_cut[None, :]
        with jax.named_scope("bfs.reduce"):
            nxt = jax.ops.segment_max(contrib.astype(ftype), g.dst,
                                      num_segments=n_cap) > 0
        with jax.named_scope("bfs.update"):
            nxt = nxt & admit & ~visited & ~hit[None, :]
            new_hit = nxt[v, lanes]       # new: nxt was masked by ~hit
            hit = hit | new_hit
            visited = visited | nxt
            if counted:
                return (nxt, visited, hit, it + 1,
                        jnp.where(new_hit, it + 1, state[4]))
        return nxt, visited, hit, it + 1

    state = (frontier, visited, hit, jnp.int32(0))
    if counted:
        state += (jnp.zeros((qc,), jnp.int32),)
    state = jax.lax.while_loop(cond, body, state)
    if counted:
        return level_table(state[4], state[3])
    return state[2]


def query(g: Graph, p: PackedLabels, u, v, *, n_cap: int,
          bfs_chunk: int = 64, max_iters: int = 256,
          return_stats: bool = False, dirty: bool = False, il=None):
    """Full Alg 2 over a query batch — the HOST-SIDE reference driver.

    Materializes verdicts on the host, slices unknowns with numpy, and
    re-dispatches one BFS chunk at a time.  Kept as the differential-testing
    oracle for ``repro.serve.engine.QueryEngine``, which runs the same
    pipeline device-resident; production callers should prefer the engine.

    ``dirty=True`` runs the fully-dynamic downgrade path: labels carry
    un-rebuilt deletions, so only self-positives and BL negatives answer
    from labels, everything else rides the live-edge BFS with the DL prune
    disabled (tombstoned edges are masked out of traversal either way).

    ``il`` threads the interval family's planes through both phases; the
    dirty path drops them entirely (while_dirty="none" — the family
    contributes nothing until the rebuild repairs it).
    """
    u = jnp.asarray(u, jnp.int32)
    v = jnp.asarray(v, jnp.int32)
    if dirty:
        verdicts = np.asarray(dirty_label_verdicts(p, u, v))
        il = None
    else:
        verdicts = np.asarray(label_verdicts(p, u, v, il=il))
    answers = verdicts == 1
    unknown = np.flatnonzero(verdicts == -1)
    dl_clean = None if not dirty else jnp.asarray(False)
    for lo in range(0, unknown.size, bfs_chunk):
        idx = unknown[lo:lo + bfs_chunk]
        pad = bfs_chunk - idx.size
        uu = jnp.asarray(np.pad(np.asarray(u)[idx], (0, pad)), jnp.int32)
        vv = jnp.asarray(np.pad(np.asarray(v)[idx], (0, pad)), jnp.int32)
        hit = np.asarray(pruned_bfs(g, p, uu, vv, dl_clean=dl_clean, il=il,
                                    n_cap=n_cap, max_iters=max_iters))
        answers[idx] = hit[:idx.size]
    if return_stats:
        rho = 1.0 - unknown.size / max(1, verdicts.size)
        return answers, {"rho": rho, "n_bfs": int(unknown.size)}
    return answers

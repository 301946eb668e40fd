"""In-edge layout for the pull form of the BFS residue.

The push relaxation (``core.query.pruned_bfs``) scatters every edge's
frontier row onto its destination with a segment-max.  On a TPU that is a
sort of all destination ids and a serial scatter, every level.  The pull
form (``core.query.pulled_bfs``) reads the same edges the other way: each
vertex owns one row of its in-edges' source rows, and a level is a gather
of those rows followed by an OR across each row's width, with no scatter.

Rows of one width must share a table, so vertices are grouped into
*classes* by in-degree.  Class widths are geometric (1, 2, 3, 4, 6, 8, 12,
16, 24, ...), so a vertex's row holds at most 1.5x its in-degree and a
graph with hubs compiles a handful of gathers, not one per degree.  The
layout is built once per bound index snapshot, on the device, by a
program of its own (``build``):

- the BFS runs in *row order*: ``pos[x]`` is vertex x's row and ``inv`` its
  inverse; class c owns rows ``[off_c, off_c + rows_c)``, the vertices of
  in-degree 0 (and any left over, below) follow the classes, and the last row (``zero_row``) belongs
  to no vertex and stays empty, so a slot that reads it adds nothing;
- per class, ``src[c]`` and ``eid[c]`` are ``(width_c, rows_c)`` tables of
  the source rows (already mapped through ``pos``) and edge slots of each
  row's in-edges.  Padding slots read ``zero_row`` and carry the slot id
  ``m_cap``, past every live edge;
- ``tail`` lists edge slots the pull relaxes in the push form: the
  in-edges of any vertex that found no row (only when a re-layout kept
  older shapes, below), then the slots from the layout's edge count on,
  where inserted edges land.  Its length, the *capacity*, is fixed per
  shape (1/16 of the edges at a fresh build), so a stream of inserts
  compiles nothing new; slots that hold no live edge are masked
  (``tail_live``), and ``build`` returns the room left for inserts.

Row counts are rounded up to a granule, and a rebuilt layout keeps the
shapes of the one it replaces (``build``'s ``like``) whenever the
vertices that find no row in them bring at most half its tail's capacity
of in-edges, so a rebuild or a re-layout after a drift of the in-degrees
compiles nothing new.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph, edge_mask


class InEdgeLayout(NamedTuple):
    pos: jax.Array        # (n_cap,) int32: vertex -> row
    inv: jax.Array        # (R,) int32: row -> vertex (n_cap on rows of none)
    src: tuple            # per class, (width_c, rows_c) int32 source rows
    eid: tuple            # per class, (width_c, rows_c) int32 edge slots
    del_epoch: jax.Array  # () int32: the graph's delete epoch at build
    tail: jax.Array       # (T,) int32 slots relaxed by push: T is 0 or
                          # the layout's tail capacity


def class_widths(max_degree: int) -> list[int]:
    """Geometric class widths 1, 2, 3, 4, 6, 8, 12, ... up to the first
    that holds ``max_degree``."""
    widths = [1]
    while widths[-1] < max_degree:
        w = widths[-1]
        widths.append(2 if w == 1 else w * 3 // 2 if w & (w - 1) == 0
                      else w * 4 // 3)
    return widths


#: every class width an in-degree below 2**31 can need
WIDTHS = tuple(class_widths(2 ** 31 - 1))


def granule(n_cap: int) -> int:
    """Rows a class is rounded up to: 1,024 at scale, less for small
    graphs so their padding stays near their size."""
    return min(1024, max(8, 1 << max(0, (n_cap - 1) // 256).bit_length()))


def tail_capacity(m: int, gran: int) -> int:
    """Tail slots of a fresh layout over ``m`` edges: 1/16 of them,
    rounded up to the row granule."""
    return -(-max(1, m // 16) // gran) * gran


def without_tail(layout: InEdgeLayout) -> InEdgeLayout:
    """The layout with an empty tail, for a graph with no edges past it:
    the pull then compiles no push-form relaxation."""
    return layout._replace(tail=jnp.zeros(0, jnp.int32))


def tail_live(g, tail):
    """(T,) bool: the tail slots that hold a live edge of graph ``g``."""
    return (tail < g.m) & (g.del_at[jnp.minimum(tail, g.m_cap - 1)]
                           > g.del_epoch)


def build(g: Graph, m: int, n_cap: int,
          like: InEdgeLayout | None = None) -> tuple[InEdgeLayout, int]:
    """The layout of the live edges of ``g`` (which has ``m`` edge slots
    in use; a slot dead now stays dead until a compaction, which re-binds
    and re-lays out), and the room its tail leaves for edges inserted
    from slot ``m`` on.  Runs on the device; reads back a few counts.

    ``like`` is the layout this one replaces: its shapes (class widths,
    row counts, tail capacity) are kept when the in-edges of the vertices
    that find no row in them fill at most half its tail; those edges ride
    the tail.  Otherwise the shapes are drawn afresh from the in-degrees:
    one class per geometric width that some in-degree needs, its row count
    rounded up to the granule, and a tail of ``tail_capacity(m)``.  When
    they replace a ``like`` that no longer fits, each class gets an eighth
    more rows than it needs, so in-degrees that keep drifting (an insert
    stream) change the shapes, and compile new programs, rarely."""
    if like is not None and like.pos.shape[0] == n_cap:
        cap = like.tail.shape[0]
        layout, n_over = _lay_out(
            g, n_cap=n_cap, widths=tuple(t.shape[0] for t in like.src),
            rows=tuple(t.shape[1] for t in like.src), cap=cap)
        n_over = int(n_over)
        if 2 * n_over <= cap:
            return layout, cap - n_over
    counts = [int(c) for c in np.asarray(_class_counts(g, n_cap=n_cap))]
    if like is not None:
        counts = [c + c // 8 for c in counts]
    gran = granule(n_cap)
    cap = tail_capacity(m, gran)
    layout, _ = _lay_out(
        g, n_cap=n_cap, widths=tuple(w for w, c in zip(WIDTHS, counts) if c),
        rows=tuple(-(-c // gran) * gran for c in counts if c), cap=cap)
    return layout, cap


def _in_degrees(g: Graph, n_cap: int) -> jax.Array:
    """(n_cap,) int32: live in-edges per vertex."""
    live = edge_mask(g)
    head = jnp.where(live, g.dst, n_cap)
    return jnp.zeros(n_cap + 1, jnp.int32).at[head].add(1)[:n_cap]


@functools.partial(jax.jit, static_argnames=("n_cap",))
def _class_counts(g: Graph, *, n_cap: int) -> jax.Array:
    """(len(WIDTHS),) int32: vertices per class of a fresh layout, each
    in the narrowest class that holds its in-degree (none for 0)."""
    deg = _in_degrees(g, n_cap)
    cls = jnp.searchsorted(jnp.asarray(WIDTHS[:-1], jnp.int32), deg)
    return jnp.zeros(len(WIDTHS), jnp.int32).at[cls].add(
        (deg > 0).astype(jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("n_cap", "widths", "rows", "cap"))
def _lay_out(g: Graph, *, n_cap: int, widths: tuple, rows: tuple,
             cap: int) -> tuple[InEdgeLayout, jax.Array]:
    """The layout of ``g``'s live edges in the given shapes, and how many
    live edges it leaves for the tail (their heads found no row).

    Vertices fit when, for every class c, those too wide for the classes
    below c are no more than the rows of c and above (class c holds
    in-degrees up to its width).  Going down from the widest class, the
    vertices that break that are left over, the lowest in-degrees among
    the ones it counts first (their rows would carry the fewest edges),
    and any wider than the widest class.  The rest fill the classes from
    the narrowest, each taking, in order of in-degree, what it holds and
    fits.  The vertices with no row in a class (in-degree 0, or left over)
    take the rows after the classes, then the classes' spare rows."""
    i32 = jnp.int32
    n_cls = len(widths)
    gran = granule(n_cap)
    n_rows = -(-(n_cap + 1) // gran) * gran + n_cls * gran
    zero_row = n_rows - 1
    off = np.concatenate([[0], np.cumsum(rows, dtype=np.int64)]).tolist()
    m_cap = g.src.shape[0]

    # vertices in order of in-degree (ties by id)
    deg = _in_degrees(g, n_cap)
    order = jnp.argsort(deg, stable=True)
    ds = deg[order]
    j = jnp.arange(n_cap, dtype=i32)
    some = j >= jnp.searchsorted(ds, 0, side="right")      # in-degree > 0
    over = j >= jnp.searchsorted(ds, widths[-1] if widths else 0,
                                 side="right")
    room = 0
    for c in reversed(range(n_cls)):
        room += rows[c]
        cand = (j >= jnp.searchsorted(ds, widths[c - 1] if c else 0,
                                      side="right")) & ~over
        k = jnp.cumsum(cand, dtype=i32)
        over |= cand & (k <= k[-1] - room)
    kept = some & ~over
    ki = jnp.cumsum(kept, dtype=i32) - 1
    row = jnp.full(n_cap, -1, i32)
    done, used = i32(0), []
    for c in range(n_cls):
        k_c = jnp.clip(jnp.sum(kept & (ds <= widths[c]), dtype=i32) - done,
                       0, rows[c])
        row = jnp.where(kept & (ki >= done) & (ki < done + k_c),
                        off[c] + ki - done, row)
        used.append(k_c)
        done = done + k_c
    placed = row >= 0
    ri = jnp.cumsum(~placed, dtype=i32) - 1
    spare = [(off[n_cls], i32(zero_row - off[n_cls]))]
    spare += [(off[c] + used[c], rows[c] - used[c]) for c in range(n_cls)]
    start = i32(0)
    for first, length in spare:
        row = jnp.where(~placed & (ri >= start) & (ri < start + length),
                        first + ri - start, row)
        start = start + length
    pos = jnp.zeros(n_cap, i32).at[order].set(row)
    inv = jnp.full(n_rows, n_cap, i32).at[pos].set(j)
    on_class = jnp.zeros(n_cap, jnp.bool_).at[order].set(placed)

    # each live edge of a placed head goes to slot (rank among its head's
    # in-edges, column) of its class; the left-over heads' edges to the tail
    live = edge_mask(g)
    eid = jnp.arange(m_cap, dtype=i32)
    head = jnp.minimum(g.dst, n_cap - 1)
    on_row = live & on_class[head]
    src_t = eid_t = ()
    if n_cls:
        key = jnp.where(on_row, head, n_cap)
        e_order = jnp.argsort(key, stable=True)
        ks = key[e_order]
        hs = jnp.minimum(ks, n_cap - 1)
        d_on = jnp.where(on_class, deg, 0)
        rank = eid - (jnp.cumsum(d_on, dtype=i32) - d_on)[hs]
        h_row = pos[hs]
        cls = jnp.searchsorted(jnp.asarray(off[1:-1], i32), h_row,
                               side="right")
        sizes = [w * r for w, r in zip(widths, rows)]
        base = np.concatenate([[0], np.cumsum(sizes)]).tolist()

        def per_class(v):
            return jnp.asarray(v, i32)[cls]
        flat = jnp.where(ks < n_cap, per_class(base[:-1])
                         + rank * per_class(rows) + h_row
                         - per_class(off[:-1]), base[-1])

        def tables(values, fill):
            t = jnp.full(base[-1], fill, i32).at[flat].set(values,
                                                          mode="drop")
            return tuple(t[base[c]:base[c + 1]].reshape(widths[c], rows[c])
                         for c in range(n_cls))
        src_t = tables(pos[jnp.minimum(g.src[e_order], n_cap - 1)],
                       zero_row)
        eid_t = tables(e_order, m_cap)

    to_tail = live & ~on_row
    n_over = jnp.sum(to_tail, dtype=i32)
    t = jnp.arange(cap, dtype=i32)
    tail = jnp.zeros(cap, i32).at[
        jnp.where(to_tail, jnp.cumsum(to_tail, dtype=i32) - 1, cap)].set(
            eid, mode="drop")
    tail = jnp.where(t < n_over, tail, g.m + t - n_over)
    return InEdgeLayout(pos, inv, src_t, eid_t, g.del_epoch, tail), n_over

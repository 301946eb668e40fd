"""Edge-insertion index maintenance (paper Algorithm 3, batched).

Inserting (u, v): every landmark reaching u now reaches Des(v); every landmark
reachable from v is now reachable from Anc(u).  Batched over b edges:

  1. append edges (the fixpoint then runs over the *updated* edge set, so
     cascades across new edges — including SCC merges — are handled);
  2. seed: OR ``DL_in[u_i]`` into ``DL_in[v_i]`` (segment-OR when several
     edges target one vertex) — Alg 3 line 1's early exit falls out naturally:
     if the seed adds no bits, the vertex never enters the frontier;
  3. run the frontier-pruned fixpoint (Alg 3 lines 2-8: the frontier *is* the
     non-subsumed set);
  4. symmetric for DL_out on the reverse graph; same for BL_in / BL_out.

No DAG is consulted at any point — this is the paper's core claim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import graph as G
from .propagate import propagate, seed_scatter_or


def insert_seeds(plane: jax.Array, new_src: jax.Array, new_dst: jax.Array,
                 *, n_cap: int, reverse: bool = False,
                 plane_repr: str = "bool"):
    """Alg-3 seeding for one plane family: for each inserted edge (u, v),
    OR ``plane[u]`` into ``plane[v]`` (roles swapped for the reverse/out
    direction).  Returns (seeded plane, changed-row frontier).  This is the
    replicated-layout op; ``core.planes.sharded_seed_scatter`` is its
    vertex-sharded twin (one psum for the gathered rows, shard-local
    scatter) — both produce bitwise-identical seeded state."""
    at_src, at_dst = (new_dst, new_src) if reverse else (new_src, new_dst)
    return seed_scatter_or(plane, plane[at_src], at_dst, n_cap,
                           plane_repr=plane_repr)


@functools.partial(jax.jit, static_argnames=("n_cap", "max_iters",
                                             "plane_repr"))
def insert_and_update(g: G.Graph,
                      dl_in, dl_out, bl_in, bl_out,
                      new_src: jax.Array, new_dst: jax.Array,
                      epoch: jax.Array | int = 0,
                      *, n_cap: int, max_iters: int = 256,
                      plane_repr: str = "bool"):
    """Returns (graph', dl_in', dl_out', bl_in', bl_out', iters (4,), epoch').

    ``epoch`` is the snapshot counter threaded through every insert batch:
    each call defines one new *snapshot epoch* (epoch' = epoch + 1).  Because
    edges are append-only, the pair (epoch, edge count m) identifies the
    exact edge set visible at that snapshot — the QueryEngine uses this to
    coalesce BFS residues across epochs with per-lane edge-count cutoffs
    instead of flushing on every index mutation.
    """
    g2 = G.insert_edges(g, new_src, new_dst)
    live = G.edge_mask(g2)

    def fwd(plane):
        seeded, frontier = insert_seeds(plane, new_src, new_dst, n_cap=n_cap,
                                        plane_repr=plane_repr)
        return propagate(seeded, g2.src, g2.dst, live, frontier,
                         n_cap=n_cap, monoid="or", max_iters=max_iters,
                         plane_repr=plane_repr)

    def bwd(plane):
        seeded, frontier = insert_seeds(plane, new_src, new_dst, n_cap=n_cap,
                                        reverse=True, plane_repr=plane_repr)
        return propagate(seeded, g2.src, g2.dst, live, frontier,
                         n_cap=n_cap, monoid="or", max_iters=max_iters,
                         reverse=True, plane_repr=plane_repr)

    dl_in2, it0 = fwd(dl_in)
    dl_out2, it1 = bwd(dl_out)
    bl_in2, it2 = fwd(bl_in)
    bl_out2, it3 = bwd(bl_out)
    iters = jnp.stack([it0, it1, it2, it3])
    epoch2 = jnp.asarray(epoch, jnp.int32) + jnp.int32(1)
    return g2, dl_in2, dl_out2, bl_in2, bl_out2, iters, epoch2


@functools.partial(jax.jit, static_argnames=("family", "n_cap", "max_iters"))
def insert_update_plugin(family: str, g2: G.Graph, p_in, p_out,
                         new_src: jax.Array, new_dst: jax.Array,
                         *, n_cap: int, max_iters: int = 256):
    """Alg-3 maintenance for one plug-in label family (``core.families``
    registry): dispatches to the family's ``insert_update`` hook under one
    jit (one executable per (family, plane shapes)).  ``g2`` must already
    contain the new edges — run this AFTER ``insert_and_update``, whose
    7-tuple contract is deliberately left untouched.  Returns
    (p_in', p_out', iters)."""
    from . import families as F
    fam = F.get(family)
    return fam.insert_update(g2, p_in, p_out, new_src, new_dst,
                             n_cap=n_cap, max_iters=max_iters)


def saturated(iters: jax.Array, max_iters: int) -> jax.Array:
    """() bool — True when any label plane's fixpoint was cut off at
    ``max_iters`` without converging (``propagate`` reports a truncated run
    as ``max_iters + 1``, so converging in exactly ``max_iters`` rounds is
    NOT saturation).  A saturated update leaves labels silently stale
    (missing bits => query FALSE negatives), so callers must surface it:
    ``DBLIndex.insert_edges`` warns (or raises in strict mode) and folds it
    into the index's ``saturated`` flag."""
    return jnp.any(iters > jnp.int32(max_iters))


def rounds(iters: jax.Array, max_iters: int) -> jax.Array:
    """() int32 — the relaxation rounds a set of fixpoints ran, summed
    (they run one after another).  A fixpoint cut off at ``max_iters``
    reports ``max_iters + 1`` (``saturated``) but ran ``max_iters``."""
    return jnp.minimum(jnp.asarray(iters, jnp.int32), max_iters).sum()


@jax.jit
def delete_and_mark(g: G.Graph, del_src: jax.Array, del_dst: jax.Array,
                    epoch: jax.Array | int = 0):
    """Returns (graph', epoch').  Tombstones the matching live edges and bumps
    BOTH clocks: the graph's ``del_epoch`` (one delete batch) and the snapshot
    ``epoch`` (a delete batch is a new snapshot, same as an insert batch).

    Deliberately does NOT touch labels — that is the fully-dynamic design:
    deletions only *shrink* reachability, so existing labels stay a sound
    over-approximation.  Label-based FALSE verdicts (BL containment) remain
    valid forever; label-based TRUE verdicts (DL intersection) and the
    theorem-1/2 negative rules become optimistic and must be downgraded to
    "unknown -> BFS over live edges" until a rebuild (see ``core.query``).
    """
    g2 = G.delete_edges(g, del_src, del_dst)
    epoch2 = jnp.asarray(epoch, jnp.int32) + jnp.int32(1)
    return g2, epoch2

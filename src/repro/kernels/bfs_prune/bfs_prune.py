"""Pallas TPU kernel: fused BFS admit-plane (Alg 2 lines 20/22 hoisted).

For a chunk of Q unresolved queries, computes admit[x, q] for all vertices x
without ever materializing the (n, Q, W) broadcast the naive jnp version
needs: the word loop is unrolled in registers/VMEM, so HBM traffic is
(W·n + W·Q) words in + n·Q bytes out — the information-theoretic minimum.

Grid (n_blocks, q_blocks); each step holds (W, NB) vertex-plane blocks and
(W, QB) query blocks in VMEM and emits one (NB, QB) admit tile.  The vertex
planes are re-streamed once per query block — q_blocks is kept small (queries
are chunked upstream) so the total traffic stays ~one pass over the planes.

Epoch-coalesced serving adds a per-lane *edge-count cutoff* operand
(``m_cut`` (1, Q) int32 against ``m_total`` (1, 1) int32, the newest edge
count): a lane whose cutoff is stale (m_cut < m_total) is being resolved
"as of" an older snapshot by a BFS restricted to its old edge prefix, and
for such lanes the DL-intersection prune is unsound (its proof needs the
lane's verdict to be non-positive at the *same* snapshot as the labels), so
the kernel drops the ``d`` term for them.  The BL containment prunes are
monotone-safe and stay on for every lane.  Fresh lanes (m_cut >= m_total)
get the full admit plane — bit-identical to the cutoff-free kernel.

Fully-dynamic serving adds the *tombstone* operand pair (``d_cut`` (1, Q)
int32 against ``d_total`` (1, 1) int32, the newest delete epoch): labels
that have not been rebuilt since a delete batch over-approximate
reachability, so the DL-intersection evidence can be stale and the ``d``
term drops for deletion-stale lanes too.  The BL containment prunes remain
sound under tombstones — bits are never removed, and the edge-wise label
coherence invariant holds along every live path — so they stay on.

Layout on the chip: a tile is (NB, QB) with queries on lanes, so every
vertex-side word must become a column and every query-side word a row.  The
kernel loads word-major (W, NB) vertex blocks (lane-dense DMA), transposes
them in VMEM and takes (NB, 1) lane slices; query rows are (1, QB) ref
slices.  The cutoffs are pre-combined in XLA into one (1, Q) uint32 mask row
(all ones = fresh, zero = DL prune off), so the whole prune is uint32 word
algebra with a single compare at the end — the TPU lowers no select between
bool operands of a 32-bit layout into an int8 tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._pad import check_cut_args


def _fresh_mask(m_cut, m_total, d_cut, d_total, q: int):
    """(1, Q) uint32 DL-prune gate: all ones on fresh lanes, 0 on stale
    ones (m- and d-cutoff both gate the same DL term, so one row suffices).
    None when no cutoffs are given."""
    if m_cut is None:
        return None
    fresh = (jnp.reshape(m_cut, (1, q)).astype(jnp.int32)
             >= jnp.reshape(m_total, ()).astype(jnp.int32))
    if d_cut is not None:
        fresh &= (jnp.reshape(d_cut, (1, q)).astype(jnp.int32)
                  >= jnp.reshape(d_total, ()).astype(jnp.int32))
    return jnp.where(fresh, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))


def _admit_tile(bia, boa, dia, row, fresh):
    """One (NB, QB) int8 admit tile.

    ``bia``/``boa`` (Wb, NB) and ``dia`` (Wd, NB) are word-major vertex
    values; ``row(name, w)`` loads query word ``w`` of ``"biv"``, ``"bov"``
    or ``"dou"`` as a (1, QB) row; ``fresh`` is the (1, QB) uint32 gate or
    None.  Shared verbatim by the grid and the streamed kernel."""
    wb, wd = bia.shape[0], dia.shape[0]
    bia, boa, dia = bia.T, boa.T, dia.T          # (NB, W): words on lanes
    # any set bit in ``bad`` rejects x for lane q: a BL containment
    # violation (c1/c2) or a DL intersection (the d term)
    bad = None
    for w in range(wb):  # static unroll: W is k'/32 (tiny)
        t = ((bia[:, w:w + 1] & ~row("biv", w))
             | (row("bov", w) & ~boa[:, w:w + 1]))
        bad = t if bad is None else bad | t
    for w in range(wd):
        t = row("dou", w) & dia[:, w:w + 1]
        if fresh is not None:
            t = t & fresh
        bad = bad | t
    return (bad == jnp.uint32(0)).astype(jnp.int8)


def _make_kernel(with_cut: bool):
    def kernel(blin_all, blout_all, dlin_all, blin_v, blout_v, dlo_u,
               *rest):
        out = rest[-1]
        q_refs = {"biv": blin_v, "bov": blout_v, "dou": dlo_u}
        out[...] = _admit_tile(
            blin_all[...], blout_all[...], dlin_all[...],
            lambda name, w: q_refs[name][pl.ds(w, 1), :],
            rest[0][...] if with_cut else None)
    return kernel


@functools.partial(jax.jit, static_argnames=("n_block", "q_block", "interpret"))
def bfs_admit_plane(blin_all, blout_all, dlin_all, blin_v, blout_v, dlo_u,
                    m_cut=None, m_total=None, d_cut=None, d_total=None,
                    *, n_block: int = 1024, q_block: int = 128,
                    interpret: bool) -> jax.Array:
    """word-major inputs: *_all (W, n); per-query (W, Q). -> (n, Q) int8.

    ``interpret`` is required: True runs the Pallas interpreter (CPU
    tests), False compiles the kernel for the TPU.

    Optional ``m_cut`` (1, Q) int32 per-lane edge-count cutoff and
    ``m_total`` (1, 1) int32 newest edge count: stale lanes
    (m_cut < m_total) lose the DL prune (see module docstring).  Omitting
    both reproduces the cutoff-free plane exactly.

    Optional ``d_cut`` (1, Q) int32 per-lane tombstone cutoff and
    ``d_total`` (1, 1) int32 newest delete epoch (requires the m-cut
    pair): lanes answered from deletion-stale labels (d_cut < d_total)
    lose the DL prune as well; the BL containment prunes stay on for
    every lane (sound under deletions — see module docstring).
    """
    wb, n = blin_all.shape
    wd = dlin_all.shape[0]
    q = blin_v.shape[1]
    assert n % n_block == 0 and q % q_block == 0, (n, n_block, q, q_block)
    check_cut_args(m_cut, m_total, d_cut, d_total)

    in_specs = [
        pl.BlockSpec((wb, n_block), lambda i, j: (0, i)),
        pl.BlockSpec((wb, n_block), lambda i, j: (0, i)),
        pl.BlockSpec((wd, n_block), lambda i, j: (0, i)),
        pl.BlockSpec((wb, q_block), lambda i, j: (0, j)),
        pl.BlockSpec((wb, q_block), lambda i, j: (0, j)),
        pl.BlockSpec((wd, q_block), lambda i, j: (0, j)),
    ]
    args = [blin_all, blout_all, dlin_all, blin_v, blout_v, dlo_u]
    fresh = _fresh_mask(m_cut, m_total, d_cut, d_total, q)
    if fresh is not None:
        in_specs.append(pl.BlockSpec((1, q_block), lambda i, j: (0, j)))
        args.append(fresh)

    return pl.pallas_call(
        _make_kernel(fresh is not None),
        grid=(n // n_block, q // q_block),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((n_block, q_block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, q), jnp.int8),
        interpret=interpret,
    )(*args)


# ------------------------------------------------- streamed (double-buffered)
def _make_streamed_kernel(with_cut: bool):
    """Single-program admit-plane kernel streaming the VERTEX axis: the
    query-side operands (a few (W, Q) blocks) are DMA'd into VMEM once,
    then the big word-major vertex planes ride a two-slot HBM→VMEM pipeline
    — chunk ``i+1``'s copy overlaps chunk ``i``'s (NB, Q) tile compute, and
    each tile's DMA back to HBM overlaps the next compute.  The prune
    algebra is ``_admit_tile``, shared with the grid kernel."""
    def kernel(bl_h, dl_h, qbl_h, qdl_h, *rest):
        out_h = rest[-1]
        nchunks, _, wb, nb = bl_h.shape
        wd = dl_h.shape[1]
        qb = qbl_h.shape[2]
        q_srcs = [qbl_h, qdl_h] + ([rest[0]] if with_cut else [])

        def body(bl_s, dl_s, qbl_s, qdl_s, fr_s, o_s, in_sem, q_sem,
                 out_sem):
            q_dsts = [qbl_s, qdl_s, fr_s]
            qcps = [pltpu.make_async_copy(src, dst, q_sem.at[j])
                    for j, (src, dst) in enumerate(zip(q_srcs, q_dsts))]
            for c in qcps:
                c.start()
            for c in qcps:
                c.wait()

            def copies(ci, slot):
                return [pltpu.make_async_copy(bl_h.at[ci], bl_s.at[slot],
                                              in_sem.at[slot, 0]),
                        pltpu.make_async_copy(dl_h.at[ci], dl_s.at[slot],
                                              in_sem.at[slot, 1])]

            for c in copies(0, 0):
                c.start()

            def row(name, w):
                if name == "dou":
                    return qdl_s[pl.ds(w, 1), :]
                return qbl_s[0 if name == "biv" else 1, pl.ds(w, 1), :]

            def step(ci, carry):
                slot = jax.lax.rem(ci, 2)

                @pl.when(ci + 1 < nchunks)
                def _():
                    for c in copies(ci + 1, 1 - slot):
                        c.start()

                for c in copies(ci, slot):
                    c.wait()
                tile = _admit_tile(bl_s[slot, 0], bl_s[slot, 1],
                                   dl_s[slot], row,
                                   fr_s[...] if with_cut else None)

                @pl.when(ci >= 2)
                def _():
                    pltpu.make_async_copy(o_s.at[slot], out_h.at[ci - 2],
                                          out_sem.at[slot]).wait()
                o_s[slot] = tile
                pltpu.make_async_copy(o_s.at[slot], out_h.at[ci],
                                      out_sem.at[slot]).start()
                return carry

            jax.lax.fori_loop(0, nchunks, step, 0)
            for ci in range(max(0, nchunks - 2), nchunks):
                pltpu.make_async_copy(o_s.at[ci % 2], out_h.at[ci],
                                      out_sem.at[ci % 2]).wait()

        pl.run_scoped(body,
                      pltpu.VMEM((2, 2, wb, nb), jnp.uint32),
                      pltpu.VMEM((2, wd, nb), jnp.uint32),
                      pltpu.VMEM((2, wb, qb), jnp.uint32),
                      pltpu.VMEM((wd, qb), jnp.uint32),
                      pltpu.VMEM((1, qb), jnp.uint32),
                      pltpu.VMEM((2, nb, qb), jnp.int8),
                      pltpu.SemaphoreType.DMA((2, 2)),
                      pltpu.SemaphoreType.DMA((len(q_srcs),)),
                      pltpu.SemaphoreType.DMA((2,)))
    return kernel


@functools.partial(jax.jit, static_argnames=("n_block", "interpret"))
def bfs_admit_plane_streamed(blin_all, blout_all, dlin_all,
                             blin_v, blout_v, dlo_u,
                             m_cut=None, m_total=None,
                             d_cut=None, d_total=None,
                             *, n_block: int = 1024,
                             interpret: bool) -> jax.Array:
    """Double-buffered variant of ``bfs_admit_plane`` — same contract,
    bitwise-identical (n, Q) int8 plane.  The vertex axis is chunked into
    ``n_block`` rows and streamed while the query-side operands stay
    resident in VMEM; there is no ``q_block`` (the residue Q is already
    chunked upstream, so one tile spans the full query width)."""
    wb, n = blin_all.shape
    wd = dlin_all.shape[0]
    q = blin_v.shape[1]
    assert n % n_block == 0, (n, n_block)
    check_cut_args(m_cut, m_total, d_cut, d_total)
    nchunks = n // n_block
    bl = jnp.stack([blin_all, blout_all])
    bl = bl.reshape(2, wb, nchunks, n_block).transpose(2, 0, 1, 3)
    dl = dlin_all.reshape(wd, nchunks, n_block).transpose(1, 0, 2)
    args = [bl, dl, jnp.stack([blin_v, blout_v]), dlo_u]
    fresh = _fresh_mask(m_cut, m_total, d_cut, d_total, q)
    if fresh is not None:
        args.append(fresh)
    out = pl.pallas_call(
        _make_streamed_kernel(fresh is not None),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(args),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((nchunks, n_block, q), jnp.int8),
        interpret=interpret,
    )(*args)
    return out.reshape(n, q)

"""Pallas TPU kernel: fused DBL label verdict (Alg 2 lines 6-13).

Eight packed uint32 label streams -> one int32 verdict per query, in a single
pass through VMEM.  This is the ρ>95% fast path of the paper, and it is
memory-bound: per query we touch 4·Wd + 4·Wb words and emit 1, so the roofline
is HBM bandwidth; the kernel's job is to reach it by (a) streaming each word
exactly once, (b) fusing all four rules so no (Q, W) intermediates ever hit
HBM, and (c) a word-major (W, Q) layout that puts queries on the 128-wide VPU
lanes and words on sublanes (the reduction axis).

Block shape: (W, QB) per stream with QB a multiple of 128 (or the whole query
axis); W is tiny (k/32, e.g. 2 for k=64) so a block is a few KB and many grid
steps stay resident in VMEM while the DMA pipeline streams the next blocks.

Per-lane operands travel as 2-D ``(1, Q)`` rows, never 1-D vectors: the TPU
compiler tiles a 1-D int32 array differently from a Mosaic block of it.  They
ride in one ``(R, 1, Q)`` int32 *flag stack* — row 0 is the self-query flag,
rows 1 and 2 (when present) the 0/1 freshness of the two staleness cutoffs —
so the kernel indexes rows along an untiled leading axis and reads no
scalars.  The cutoff comparisons happen in XLA in front of the call.

Fully-dynamic serving adds a second per-lane cutoff operand pair alongside
the edge-count cutoff: ``d_cut`` (Q,) int32 against ``d_total`` (1,) int32
(the newest tombstone delete epoch).  A lane with ``d_cut < d_total`` is
answered from labels that have NOT been rebuilt since some delete batch —
the labels over-approximate reachability, so the kernel downgrades every
verdict resting on positive label evidence (DL positives, theorem-1/2
negatives) to unknown and keeps only self-positives and BL-containment
negatives (sound under deletion: bits are never removed, so completeness —
all the BL rule needs — is preserved).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._pad import check_cut_args


def _flag_stack(same, m_cut, m_total, d_cut, d_total):
    """(R, 1, Q) int32 per-lane flags: [u == v, m-fresh?, d-fresh?] as 0/1."""
    rows = [same.astype(jnp.int32)]
    for cut, total in ((m_cut, m_total), (d_cut, d_total)):
        if cut is not None:
            tot = jnp.reshape(total, ()).astype(jnp.int32)
            rows.append((cut.astype(jnp.int32) >= tot).astype(jnp.int32))
    q = same.shape[0]
    return jnp.stack([jnp.reshape(r, (1, q)) for r in rows])


def _verdict(dl, bl, flags, il=None):
    """The fused Alg-2 verdict of one (·, QB) tile -> (1, QB) int32.

    ``dl`` = (dlo_u, dli_v, dlo_v, dli_u) and ``bl`` = (blin_u, blin_v,
    blout_u, blout_v) are (W, QB) uint32 word-major values; ``flags`` the
    (1, QB) int32 rows of the flag stack; ``il`` four (2*dim, QB) int32 rank
    rows or None.  Shared verbatim by the grid and the streamed kernel."""
    dlo_u, dli_v, dlo_v, dli_u = dl
    blin_u, blin_v, blout_u, blout_v = bl
    z = jnp.uint32(0)

    def any_word(x):
        return jnp.any(x != z, axis=0, keepdims=True)

    is_same = flags[0] != 0
    pos_lbl = any_word(dlo_u & dli_v)
    pos = pos_lbl | is_same
    bl_neg = any_word(blin_u & ~blin_v) | any_word(blout_v & ~blout_u)
    thm = (any_word(dlo_v & dli_u)
           | any_word(dlo_u & dli_u) | any_word(dlo_v & dli_v))
    neg_lbl = bl_neg
    if il is not None:
        # interval containment violation (plug-in negative prune): a pure
        # elementwise greater-than sweep over the rank sublanes.  Insert-
        # monotone like BL, so it skips the m-cut; it joins ONLY the
        # d-fresh branch below (contributes nothing while dirty).  Padding
        # lanes carry rank 0 on both sides: 0 > 0 never prunes.
        ilo_u, ilo_v, ili_u, ili_v = il
        neg_lbl = (neg_lbl | jnp.any(ilo_u > ilo_v, axis=0, keepdims=True)
                   | jnp.any(ili_v > ili_u, axis=0, keepdims=True))
    neg = ~pos & (neg_lbl | thm)
    if len(flags) > 1:
        # per-lane edge-count cutoff: a positive proven only by labels
        # NEWER than the lane's snapshot (stale lane) may ride edges the
        # snapshot did not have — downgrade it to unknown; negatives and
        # self-queries are monotone-safe and survive any cutoff.
        fresh = flags[1] != 0
        if len(flags) > 2:
            # tombstone cutoff: lanes whose labels carry un-rebuilt
            # DELETIONS lose every verdict that rests on positive label
            # evidence — DL positives AND the theorem-1/2 negatives — since
            # stale bits may certify paths that no longer exist.  Only
            # self-queries and BL-containment negatives (which need
            # completeness, not exactness, and bits are never removed)
            # survive.  Written as mask algebra: a select between two bool
            # operands does not lower on the TPU.
            d_fresh = flags[2] != 0
            fresh = fresh & d_fresh
            neg = (d_fresh & neg) | (~d_fresh & ~is_same & bl_neg)
        pos = (pos_lbl & fresh) | is_same
    return jnp.where(pos, jnp.int32(1),
                     jnp.where(neg, jnp.int32(0), jnp.int32(-1)))


def _make_kernel(nflags: int, with_il: bool):
    def kernel(dlo_u, dli_v, dlo_v, dli_u,
               blin_u, blin_v, blout_u, blout_v, flags, *rest):
        il = None
        if with_il:
            # four (2*dim, QB) int32 interval-rank streams, word-major like
            # the label words: queries on lanes, interval ends on sublanes
            il = tuple(r[...] for r in rest[:4])
        out = rest[-1]
        out[...] = _verdict(
            (dlo_u[...], dli_v[...], dlo_v[...], dli_u[...]),
            (blin_u[...], blin_v[...], blout_u[...], blout_v[...]),
            [flags[r] for r in range(nflags)], il)
    return kernel


@functools.partial(jax.jit, static_argnames=("q_block", "interpret"))
def dbl_query_verdicts(dlo_u, dli_v, dlo_v, dli_u,
                       blin_u, blin_v, blout_u, blout_v, same,
                       m_cut=None, m_total=None, d_cut=None, d_total=None,
                       il_rows=None,
                       *, q_block: int = 512, interpret: bool):
    """All label args (W, Q) uint32 word-major; same (Q,) int32. -> (Q,) int32.

    Q must be a multiple of q_block (callers pad; see ops.py).
    ``interpret`` is required: True runs the Pallas interpreter (CPU
    tests), False compiles the kernel for the TPU.

    Optional ``il_rows`` = (ilo_u, ilo_v, ili_u, ili_v), four (2*dim, Q)
    int32 word-major interval-rank streams of the "il" plug-in family:
    containment violations join the negative rules in-kernel (the fused
    verdict stays one pass; +4·2·dim words per query of extra traffic).
    Like BL the interval prune skips the edge-count cutoff
    (insert-monotone), and like DL positives it is dropped entirely on
    tombstone-stale lanes (``d_cut < d_total``).

    Optional ``m_cut`` (Q,) int32 per-lane edge-count cutoff + ``m_total``
    (1,) int32 newest edge count: verdicts become valid "as of" each lane's
    cutoff — label positives on stale lanes (m_cut < m_total) degrade to
    unknown (they must ride a cutoff BFS), negatives stay (monotone under
    insert-only updates).  Omitting both is the plain snapshot verdict.

    Optional ``d_cut`` (Q,) int32 per-lane *tombstone* cutoff + ``d_total``
    (1,) int32 newest delete epoch (requires the m-cut pair): lanes whose
    labels carry un-rebuilt deletions (d_cut < d_total) keep ONLY
    self-positives and BL-containment negatives — DL positives and the
    theorem-1/2 negatives degrade to unknown and ride the live-edge BFS.
    Fresh d-cuts (d_cut >= d_total) are bitwise the m-cut-only kernel.
    """
    wd = dlo_u.shape[0]
    wb = blin_u.shape[0]
    q = dlo_u.shape[1]
    assert q % q_block == 0, (q, q_block)
    check_cut_args(m_cut, m_total, d_cut, d_total)
    flags = _flag_stack(same, m_cut, m_total, d_cut, d_total)
    nflags = flags.shape[0]

    def dl_spec():
        return pl.BlockSpec((wd, q_block), lambda i: (0, i))

    def bl_spec():
        return pl.BlockSpec((wb, q_block), lambda i: (0, i))

    in_specs = [dl_spec(), dl_spec(), dl_spec(), dl_spec(),
                bl_spec(), bl_spec(), bl_spec(), bl_spec(),
                pl.BlockSpec((nflags, 1, q_block), lambda i: (0, 0, i))]
    args = [dlo_u, dli_v, dlo_v, dli_u,
            blin_u, blin_v, blout_u, blout_v, flags]
    with_il = il_rows is not None
    if with_il:
        wi = il_rows[0].shape[0]
        in_specs += [pl.BlockSpec((wi, q_block), lambda i: (0, i))] * 4
        args += [r.astype(jnp.int32) for r in il_rows]

    out = pl.pallas_call(
        _make_kernel(nflags, with_il),
        grid=(q // q_block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, q_block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, q), jnp.int32),
        interpret=interpret,
    )(*args)
    return out.reshape(q)


# ------------------------------------------------- streamed (double-buffered)
def _make_streamed_kernel(nflags: int):
    """Single-program kernel: all operands live in HBM (``pl.ANY``) and
    are streamed through a two-slot VMEM scratch by explicit async copies —
    while chunk ``i`` computes, chunk ``i+1``'s HBM→VMEM DMA is in flight,
    and chunk ``i``'s verdict DMA back to HBM overlaps the next compute
    (its semaphore is only awaited when the slot comes around again).

    Every chunk operand carries its chunk index on a leading untiled axis —
    (4, W, QB) label stacks, the (R, 1, QB) flag stack, a (1, QB) verdict
    row — so slot and row selection never slices a tiled dimension.  The
    verdict algebra is ``_verdict``, shared with the grid kernel."""
    def kernel(dl_h, bl_h, fl_h, out_h):
        nchunks, _, wd, qb = dl_h.shape
        wb = bl_h.shape[2]

        def body(dl_s, bl_s, fl_s, o_s, in_sem, out_sem):
            def copies(ci, slot):
                return [pltpu.make_async_copy(src.at[ci], dst.at[slot],
                                              in_sem.at[j, slot])
                        for j, (src, dst) in enumerate(
                            ((dl_h, dl_s), (bl_h, bl_s), (fl_h, fl_s)))]

            for c in copies(0, 0):
                c.start()

            def step(ci, carry):
                slot = jax.lax.rem(ci, 2)

                @pl.when(ci + 1 < nchunks)
                def _():
                    for c in copies(ci + 1, 1 - slot):
                        c.start()

                for c in copies(ci, slot):
                    c.wait()
                # dl: dlo_u dli_v dlo_v dli_u   bl: bi_u bi_v bo_u bo_v
                verd = _verdict(
                    tuple(dl_s[slot, j] for j in range(4)),
                    tuple(bl_s[slot, j] for j in range(4)),
                    [fl_s[slot, r] for r in range(nflags)])

                # the slot's previous verdict DMA (chunk ci-2) must have
                # landed before its buffer is overwritten
                @pl.when(ci >= 2)
                def _():
                    pltpu.make_async_copy(o_s.at[slot], out_h.at[ci - 2],
                                          out_sem.at[slot]).wait()
                o_s[slot] = verd
                pltpu.make_async_copy(o_s.at[slot], out_h.at[ci],
                                      out_sem.at[slot]).start()
                return carry

            jax.lax.fori_loop(0, nchunks, step, 0)
            for ci in range(max(0, nchunks - 2), nchunks):
                pltpu.make_async_copy(o_s.at[ci % 2], out_h.at[ci],
                                      out_sem.at[ci % 2]).wait()

        pl.run_scoped(body,
                      pltpu.VMEM((2, 4, wd, qb), jnp.uint32),
                      pltpu.VMEM((2, 4, wb, qb), jnp.uint32),
                      pltpu.VMEM((2, nflags, 1, qb), jnp.int32),
                      pltpu.VMEM((2, 1, qb), jnp.int32),
                      pltpu.SemaphoreType.DMA((3, 2)),
                      pltpu.SemaphoreType.DMA((2,)))
    return kernel


@functools.partial(jax.jit, static_argnames=("q_block", "interpret"))
def dbl_query_verdicts_streamed(dlo_u, dli_v, dlo_v, dli_u,
                                blin_u, blin_v, blout_u, blout_v, same,
                                m_cut=None, m_total=None,
                                d_cut=None, d_total=None,
                                *, q_block: int = 512,
                                interpret: bool):
    """Double-buffered variant of ``dbl_query_verdicts`` — same contract,
    bitwise-identical output.  The query axis is chunked into ``q_block``
    columns and the (4, W, QB) label stacks are streamed HBM→VMEM with the
    next chunk's copy overlapping the current chunk's verdict compute (the
    grid-free ``pl.ANY`` + ``make_async_copy`` pipeline)."""
    wd = dlo_u.shape[0]
    wb = blin_u.shape[0]
    q = dlo_u.shape[1]
    assert q % q_block == 0, (q, q_block)
    check_cut_args(m_cut, m_total, d_cut, d_total)
    nchunks = q // q_block
    dl = jnp.stack([dlo_u, dli_v, dlo_v, dli_u])
    bl = jnp.stack([blin_u, blin_v, blout_u, blout_v])
    dl = dl.reshape(4, wd, nchunks, q_block).transpose(2, 0, 1, 3)
    bl = bl.reshape(4, wb, nchunks, q_block).transpose(2, 0, 1, 3)
    flags = _flag_stack(same, m_cut, m_total, d_cut, d_total)
    nflags = flags.shape[0]
    flags = flags.reshape(nflags, 1, nchunks, q_block).transpose(2, 0, 1, 3)
    out = pl.pallas_call(
        _make_streamed_kernel(nflags),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((nchunks, 1, q_block), jnp.int32),
        interpret=interpret,
    )(dl, bl, flags)
    return out.reshape(q)

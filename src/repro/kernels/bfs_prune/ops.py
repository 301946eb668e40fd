"""Jit'd wrapper: packed labels + query ids -> (n_cap, Qc) admit plane."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.query import FRESH_CUT, PackedLabels
from repro.kernels._pad import pad_axis as _pad_axis
from .bfs_prune import bfs_admit_plane, bfs_admit_plane_streamed


@functools.partial(jax.jit, static_argnames=("n_block", "q_block",
                                             "interpret", "out_dtype",
                                             "streaming"))
def admit_plane(p: PackedLabels, u: jax.Array, v: jax.Array,
                m_cut: jax.Array | None = None,
                m_total: jax.Array | None = None,
                d_cut: jax.Array | None = None,
                d_total: jax.Array | None = None,
                il=None, il_on: jax.Array | None = None,
                *, n_block: int = 1024, q_block: int = 128,
                interpret: bool,
                out_dtype=jnp.bool_, streaming: bool = False) -> jax.Array:
    """Returns (n_cap, Qc) ``out_dtype`` admit plane for the pruned-BFS
    lanes (``jnp.int8`` hands the kernel's narrow plane through without a
    widening cast; ``pruned_bfs`` re-binarizes admit planes of any dtype).

    Optional ``m_cut`` (Qc,) int32 / ``m_total`` scalar: per-lane edge-count
    cutoffs for epoch-coalesced lanes (stale lanes lose the DL prune).
    Optional ``d_cut`` (Qc,) int32 / ``d_total`` scalar: per-lane tombstone
    cutoffs (deletion-stale lanes lose the DL prune too; requires m_cut).
    Padding lanes get fresh cutoffs so they keep the default plane.
    ``streaming=True`` routes to the double-buffered grid-free kernel
    (explicit HBM→VMEM copy pipeline over the vertex axis; ``q_block``
    only pads the query axis there — the tile spans the full width).

    ``il`` = (il_in, il_out) folds the interval plug-in family's
    containment prune into the plane as an elementwise AND *around* the
    kernel output (the bit-plane kernels keep their word layout; XLA fuses
    the int32 sweep into the surrounding program).  ``il_on`` (() or (Qc,)
    bool) gates it — the engine passes its tombstone-clean flag, because
    interval negatives are insert-monotone but not deletion-sound.
    """
    n = p.bl_in.shape[0]
    q = u.shape[0]
    blin_all = _pad_axis(p.bl_in.T, n_block, 1)
    blout_all = _pad_axis(p.bl_out.T, n_block, 1)
    dlin_all = _pad_axis(p.dl_in.T, n_block, 1)
    blin_v = _pad_axis(p.bl_in[v].T, q_block, 1)
    blout_v = _pad_axis(p.bl_out[v].T, q_block, 1)
    dlo_u = _pad_axis(p.dl_out[u].T, q_block, 1)
    cut = tot = dcut = dtot = None
    if m_cut is not None:
        cut = _pad_axis(jnp.reshape(m_cut.astype(jnp.int32), (1, q)),
                        q_block, 1, value=FRESH_CUT)
        tot = jnp.reshape(jnp.asarray(m_total, jnp.int32), (1, 1))
    if d_cut is not None:
        dcut = _pad_axis(jnp.reshape(d_cut.astype(jnp.int32), (1, q)),
                         q_block, 1, value=FRESH_CUT)
        dtot = jnp.reshape(jnp.asarray(d_total, jnp.int32), (1, 1))
    if streaming:
        out = bfs_admit_plane_streamed(blin_all, blout_all, dlin_all,
                                       blin_v, blout_v, dlo_u,
                                       cut, tot, dcut, dtot,
                                       n_block=n_block, interpret=interpret)
    else:
        out = bfs_admit_plane(blin_all, blout_all, dlin_all,
                              blin_v, blout_v, dlo_u, cut, tot, dcut, dtot,
                              n_block=n_block, q_block=q_block,
                              interpret=interpret)
    out = out[:n, :q]
    if il is not None:
        il_in, il_out = il
        bad = (jnp.any(il_out[:, None, :] > il_out[v][None, :, :], axis=-1)
               | jnp.any(il_in[v][None, :, :] > il_in[:, None, :], axis=-1))
        if il_on is not None:
            bad = bad & jnp.broadcast_to(il_on, (q,))[None, :]
        out = ((out > 0) & ~bad) if out.dtype != jnp.bool_ else (out & ~bad)
    return out.astype(out_dtype)

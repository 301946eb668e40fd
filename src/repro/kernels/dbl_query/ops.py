"""Jit'd public wrapper: gather + word-major transpose + Pallas verdict kernel.

The row gathers stay in XLA (TPU has a native gather); the kernel fuses the
bitwise verdict so no (Q, W) intermediates round-trip through HBM.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core.query import FRESH_CUT, PackedLabels
from repro.kernels._pad import pad_axis as _pad_to
from .dbl_query import dbl_query_verdicts, dbl_query_verdicts_streamed

class StreamILFallbackWarning(UserWarning):
    """A streaming+il verdict dispatch fell back to the grid kernel (the
    streamed kernel's fixed copy pipeline takes no interval operands;
    verdicts are bitwise identical).  A dedicated category so callers can
    silence or escalate the fallback with the standard ``warnings``
    filters — there is no process-wide latch that would mute the signal
    for unrelated engines or threads."""


def verdicts_device(p: PackedLabels, u: jax.Array, v: jax.Array,
                    m_cut: jax.Array | None = None,
                    m_total: jax.Array | None = None,
                    d_cut: jax.Array | None = None,
                    d_total: jax.Array | None = None,
                    il=None,
                    *, q_block: int = 512, interpret: bool,
                    out_dtype=jnp.int32, streaming: bool = False
                    ) -> jax.Array:
    """Traceable (un-jitted) body of ``query_verdicts`` so larger programs —
    the QueryEngine's fused label phase — can inline it into one executable.

    ``m_cut`` (Q,) / ``m_total`` scalar thread the per-lane edge-count
    cutoff through to the kernel (stale label positives -> unknown);
    ``d_cut`` (Q,) / ``d_total`` scalar thread the tombstone cutoff
    (deletion-stale labels keep only self-positives and BL negatives).
    Padding lanes are marked fresh on both so they never ride a BFS.
    ``out_dtype=jnp.int8`` emits the engine's narrow verdict lane directly
    (values identical to the int32 path).  ``streaming=True`` routes to the
    double-buffered grid-free kernel (explicit HBM→VMEM copy pipeline,
    bitwise-identical verdicts).

    ``il`` = (il_in, il_out) threads the interval plug-in family: four more
    (2*dim, Q) int32 rank streams ride into the grid kernel and the
    containment check fuses into the same pass.  Pad lanes carry rank 0 on
    both sides of every comparison, so they never prune.  The streamed
    kernel keeps its fixed copy pipeline and takes no interval operands;
    ``streaming=True`` with ``il`` falls back to the grid kernel (identical
    verdicts), signalling a :class:`StreamILFallbackWarning` on every
    traced dispatch instead of failing it.  Jit caching means a steady
    stream warns once per compiled shape; the QueryEngine additionally
    latches it to once per engine instance."""
    if streaming and il is not None:
        warnings.warn(
            "the streamed dbl_query kernel's fixed copy pipeline takes "
            "no interval-family operands; il-enabled verdict dispatches "
            "fall back to the grid kernel (bitwise-identical verdicts)",
            StreamILFallbackWarning, stacklevel=2)
        streaming = False
    q = u.shape[0]
    streams = [p.dl_out[u], p.dl_in[v], p.dl_out[v], p.dl_in[u],
               p.bl_in[u], p.bl_in[v], p.bl_out[v], p.bl_out[u]]
    # word-major (W, Q), pad Q to a block multiple
    streams = [_pad_to(s.T, q_block, 1) for s in streams]
    same = _pad_to((u == v).astype(jnp.int32), q_block, 0)
    cut = tot = dcut = dtot = il_rows = None
    if il is not None:
        il_in, il_out = il
        il_rows = tuple(_pad_to(s.T.astype(jnp.int32), q_block, 1)
                        for s in (il_out[u], il_out[v], il_in[u], il_in[v]))
    if m_cut is not None:
        cut = _pad_to(m_cut.astype(jnp.int32), q_block, 0, value=FRESH_CUT)
        tot = jnp.asarray(m_total, jnp.int32)
    if d_cut is not None:
        dcut = _pad_to(d_cut.astype(jnp.int32), q_block, 0, value=FRESH_CUT)
        dtot = jnp.asarray(d_total, jnp.int32)
    # note arg order: kernel wants (dlo_u, dli_v, dlo_v, dli_u,
    #                               blin_u, blin_v, blout_u, blout_v)
    dlo_u, dli_v, dlo_v, dli_u, blin_u, blin_v, blout_v, blout_u = streams
    if streaming:
        out = dbl_query_verdicts_streamed(
            dlo_u, dli_v, dlo_v, dli_u,
            blin_u, blin_v, blout_u, blout_v, same,
            cut, tot, dcut, dtot,
            q_block=q_block, interpret=interpret)
    else:
        out = dbl_query_verdicts(
            dlo_u, dli_v, dlo_v, dli_u,
            blin_u, blin_v, blout_u, blout_v, same,
            cut, tot, dcut, dtot, il_rows,
            q_block=q_block, interpret=interpret)
    return out[:q].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("q_block", "interpret",
                                             "streaming"))
def query_verdicts(p: PackedLabels, u: jax.Array, v: jax.Array, il=None,
                   *, q_block: int = 512, interpret: bool,
                   streaming: bool = False) -> jax.Array:
    """(Q,) int32 verdicts; same contract as core.query.label_verdicts."""
    return verdicts_device(p, u, v, il=il, q_block=q_block,
                           interpret=interpret, streaming=streaming)

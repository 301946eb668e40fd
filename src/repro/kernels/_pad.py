"""Helpers shared by both Pallas kernel packages.

Both pad their word-major streams (and per-lane cutoff rows) up to block
multiples before the ``pallas_call`` and take the same optional cutoff
operand pairs; keeping one implementation of each stops the two packages'
semantics from drifting.
"""
from __future__ import annotations

import jax.numpy as jnp


def pad_axis(x, mult: int, axis: int, value=0):
    """Right-pad ``x`` along ``axis`` to the next multiple of ``mult`` with
    ``value`` (default 0; cutoff rows pad with ``core.query.FRESH_CUT``)."""
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value)


def check_cut_args(m_cut, m_total, d_cut, d_total):
    """The cutoff operands come in (cut, total) pairs, and the tombstone
    pair needs the edge-count pair."""
    assert (m_cut is None) == (m_total is None), \
        "pass m_cut and m_total together"
    assert (d_cut is None) == (d_total is None), \
        "pass d_cut and d_total together"
    assert d_cut is None or m_cut is not None, \
        "the tombstone cutoff requires the edge-count cutoff operands"

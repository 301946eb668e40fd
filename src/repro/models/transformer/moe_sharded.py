"""shard_map MoE: explicit all-to-all expert exchange (beyond-paper §Perf).

The pjit sort/gather dispatch is memory-clean but its cross-shard gathers
lower to activation-sized all-reduces (measured 31.7 GB/device/layer on
arctic-480b).  The napkin-optimal data movement is an all-to-all carrying
exactly the routed slots: T_local·K·d bytes per device per direction.

Layout inside shard_map (over every mesh axis):
  x      (T_loc, d)        — tokens local to a (dp, tp) cell
  router (d, E)            — replicated
  w1/w3  (E/tp, d, f), w2 (E/tp, f, d) — expert-parallel over the model axis
Per cell: local top-k routing -> local capacity buffer (E, c_cell, d) ->
all_to_all over the model axis (split experts / concat capacity) ->
local expert GLU -> reverse all_to_all -> local combine.

Capacity policy is per-cell (GShard local capacity): drop patterns differ
from the global-capacity pjit path, equality holds in the no-drop regime
(tested in tests/distributed/run_moe_sharded.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs.base import MoEConfig


def _local_moe(cfg: MoEConfig, act, n_tp: int, tp_axis: str,
               all_axes: tuple):
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // n_tp

    def fn(x, router, w1, w3, w2):
        t_loc, d = x.shape
        c = max(4, int(t_loc * k / e * cfg.capacity_factor))
        logits = jnp.einsum("td,de->te", x, router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, k)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)

        slot_e = eidx.reshape(-1)
        order = jnp.argsort(slot_e)
        se = slot_e[order]
        tok = order // k
        gate = gates.reshape(-1)[order]
        counts = jax.ops.segment_sum(jnp.ones_like(se), se, num_segments=e)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t_loc * k, dtype=jnp.int32) - starts[se]
        keep = pos < c
        row = jnp.where(keep, se * c + pos, e * c)
        tk = t_loc * k
        fill = jnp.full((e * c,), tk, jnp.int32).at[row].set(
            jnp.arange(tk, dtype=jnp.int32), mode="drop")
        src_tok = tok[jnp.minimum(fill, tk - 1)]
        buf = jnp.where((fill < tk)[:, None], jnp.take(x, src_tok, axis=0),
                        0).reshape(e, c, d)

        # ---- expert exchange: (E, c, d) -> (E/tp, tp*c, d)  [tiled a2a]
        bufx = jax.lax.all_to_all(buf, tp_axis, split_axis=0,
                                  concat_axis=1, tiled=True)

        h = jnp.einsum("ecd,edf->ecf", bufx, w1)
        g = jnp.einsum("ecd,edf->ecf", bufx, w3)
        h = (act(h.astype(jnp.float32)) * g.astype(jnp.float32)
             ).astype(x.dtype)
        out = jnp.einsum("ecf,efd->ecd", h, w2)

        # ---- reverse exchange: (E/tp, tp*c, d) -> (E, c, d)
        outx = jax.lax.all_to_all(out, tp_axis, split_axis=1,
                                  concat_axis=0, tiled=True)
        outx = outx.reshape(e * c, d)

        gate_s = jnp.where(keep, gate, 0.0).astype(x.dtype)
        vals = jnp.take(outx, jnp.minimum(row, e * c - 1), axis=0) \
            * gate_s[:, None]
        inv_order = jnp.zeros((tk,), jnp.int32).at[order].set(
            jnp.arange(tk, dtype=jnp.int32))
        y = jnp.take(vals, inv_order, axis=0).reshape(t_loc, k, d).sum(1)

        f_e = jax.ops.segment_sum(jnp.ones_like(se, jnp.float32), se,
                                  num_segments=e) / (t_loc * k)
        p_e = probs.mean(axis=0)
        aux_loc = cfg.router_aux_weight * e * jnp.sum(f_e * p_e)
        aux = jax.lax.pmean(aux_loc, all_axes)
        return y, aux

    return fn


def moe_ffn_sharded(params: dict, x: jax.Array, cfg: MoEConfig, act, *,
                    mesh, dp_axes: tuple, tp_axis: str):
    """x (T, d) global (sharded over all axes on T). Returns (y, aux).

    Shared-expert / dense-residual branches stay in pjit (plain dense FFNs
    partition well); only the routed-expert path runs under shard_map.
    """
    n_tp = dict(zip(mesh.axis_names, mesh.devices.shape))[tp_axis]
    all_axes = tuple(dp_axes) + (tp_axis,)
    local = _local_moe(cfg, act, n_tp, tp_axis, all_axes)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(all_axes, None), P(), P(tp_axis, None, None),
                  P(tp_axis, None, None), P(tp_axis, None, None)),
        out_specs=(P(all_axes, None), P()),
        check_vma=False)
    y, aux = fn(x, params["router"], params["w1"], params["w3"],
                params["w2"])

    if cfg.n_shared > 0:
        from .moe import _glu
        y = y + _glu(x, params["shared_w1"], params["shared_w3"],
                     params["shared_w2"], act)
    if cfg.dense_residual:
        from .moe import _glu
        y = y + _glu(x, params["dense_w1"], params["dense_w3"],
                     params["dense_w2"], act)
    return y, aux

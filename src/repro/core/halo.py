"""Sparse compressed halo exchange for the vertex-sharded fixpoint.

The dense halo exchange (``planes._halo_propagate_*_impl``) ships every
halo slot of every (sender, receiver) pair every round.  On power-law
graphs the boundary covers most rows, so after the first few rounds the
fixpoint pays full-cut bandwidth for a frontier that has collapsed to a
handful of rows.  This module makes the exchange sparse and
self-quenching while staying **bitwise equal to the dense oracle by
construction** — the same rounds relax the same edges with the same
monotone reductions; only the transport of boundary rows changes:

- **Active-row compaction.**  A boundary row needs to travel in round r
  iff it is in the round-r frontier (rows are monotone under OR/MIN, so
  "changed since last sent" == "in the frontier" — the popcount-diff
  against the previous round's sent values is exactly the frontier bit).
  Each round the changed rows of each pair are compacted into a
  power-of-two capacity bucket (at most two static capacities per plan,
  the same bucketing discipline as the engine's BFS chunks) and only the
  compacted (position, payload) buffers cross the mesh; receivers
  scatter-OR / scatter-MIN them back into the combined table by slot.
  Rows that do not travel are exactly the rows whose value the receiver
  already incorporates — OR/MIN identities w.r.t. the receiver's current
  state — so dropping them is lossless.
- **Overflow fallback.**  Capacities are enforced by the fixpoint's own
  loop condition: a round whose changed-row count exceeds the bucket
  capacity never executes under that capacity — the loop exits and the
  host re-enters the fixpoint under the next larger capacity (or the
  dense exchange).  SPMD collectives have one static shape per program,
  so the per-pair overflow flag promotes the *round* to the dense
  exchange rather than a single pair's slice; the result is bitwise
  identical either way, dense rounds simply cost dense bytes.
- **Hub broadcast lane.**  The top-``hub_count`` highest-cut-degree
  vertices (frozen on the :class:`planes.ShardPlan`) leave the per-pair
  buckets during sparse rounds and travel once per round on a broadcast
  psum lane: the owner contributes the row, everyone else zeros, one
  ``psum`` delivers it everywhere, and each receiver scatters it into
  its pair slot.  Hub rows are the rows most likely to be duplicated
  into up to d-1 pair buckets — the lane removes the largest rows from
  every bucket.  During dense rounds hubs ride the pair buffers exactly
  as before.
- **Quiescence gating.**  The global changed-row count (a psum in the
  loop condition) drives the fixpoint; per-pair all-quiet flags are the
  compaction counts themselves — a quiet pair's buffer carries only the
  zero-payload sentinel, and a fully-quiet mesh drops into a local
  regime with no payload collective at all, so converged regions stop
  paying bandwidth while stragglers finish.

The host drives the fixpoint as a sequence of **regimes** — jitted
shard_map while-loops specialised to one transport (dense / sparse(C) /
local) whose loop condition *also* asserts the regime still applies.
Transitions sync only a (d, d) count matrix and three scalars; steady
rounds stay on device.  :class:`HaloTelemetry` accumulates the modeled
wire bytes per round from the measured per-pair activity, for both the
dense oracle and the sparse exchange, so benchmarks compare the two on
identical round structures.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from . import bitset
from .propagate import _INT_MAX, check_plane_repr


def bucket_caps(H: int) -> tuple[int, ...]:
    """Compaction capacities for a halo width ``H``: at most two
    power-of-two bucket shapes (engine BFS-chunk discipline), both
    strictly below ``H`` so a sparse round is never wider than dense.
    Tiny halos get no sparse shapes at all — dense is already cheap."""
    if H < 16:
        return ()
    hi = 1
    while hi * 4 < H:
        hi *= 2                      # largest power of two <= H/4
    lo = max(8, hi // 8)
    return tuple(sorted({c for c in (lo, hi) if c < H}))


@dataclasses.dataclass
class HaloTelemetry:
    """Accumulated halo-exchange accounting across fixpoints.

    ``bytes`` models the wire cost of what actually crossed the mesh:
    dense rounds pay every pair's full ``H x (row + flag)`` buffer,
    sparse rounds pay ``cap x (row + 4-byte position)`` per non-quiet
    pair plus a 4-byte sentinel per pair and the hub lane's broadcast,
    local rounds pay only the liveness psum.  Dense-mode fixpoints
    record their (device-resident) round counts lazily so the engine's
    insert path never blocks on a D2H sync; :meth:`sync` drains them."""
    bytes: int = 0
    rounds: int = 0
    dense_rounds: int = 0
    sparse_rounds: int = 0
    local_rounds: int = 0
    quiet_pair_rounds: int = 0
    nonquiet_pair_rounds: int = 0
    fixpoints: int = 0
    _pending: list = dataclasses.field(default_factory=list, repr=False)

    def add_dense(self, iters, bytes_per_round: int,
                  max_iters: int) -> None:
        """Record a dense-mode fixpoint without syncing its device-
        resident iteration count."""
        self._pending.append((iters, int(bytes_per_round), int(max_iters)))

    def note_regime(self, kind: str, rounds: int, cap: int,
                    nonq_pairs: int, quiet_pairs: int, *, d: int, H: int,
                    hub_n: int, row_bytes: int) -> None:
        self.rounds += rounds
        if kind == "dense":
            self.dense_rounds += rounds
            self.bytes += rounds * d * (d - 1) * H * (row_bytes + 1)
        elif kind == "sparse":
            self.sparse_rounds += rounds
            self.bytes += nonq_pairs * cap * (row_bytes + 4)
            self.bytes += rounds * d * (d - 1) * 4        # per-pair count
            self.bytes += rounds * d * hub_n * (row_bytes + 1)  # hub lane
        else:
            self.local_rounds += rounds
            self.bytes += rounds * d * 4                  # liveness psum
        self.quiet_pair_rounds += quiet_pairs
        self.nonquiet_pair_rounds += nonq_pairs

    def sync(self) -> "HaloTelemetry":
        for iters, bpr, max_iters in self._pending:
            r = min(int(iters), max_iters)   # max_iters+1 == truncated
            self.rounds += r
            self.dense_rounds += r
            self.bytes += r * bpr
            self.fixpoints += 1
        self._pending.clear()
        return self

    def as_dict(self) -> dict:
        self.sync()
        return {"halo_bytes": int(self.bytes),
                "halo_rounds": int(self.rounds),
                "dense_rounds": int(self.dense_rounds),
                "sparse_rounds": int(self.sparse_rounds),
                "local_rounds": int(self.local_rounds),
                "quiet_pair_rounds": int(self.quiet_pair_rounds),
                "nonquiet_pair_rounds": int(self.nonquiet_pair_rounds),
                "fixpoints": int(self.fixpoints)}


def _hub_specs(ax, use_hubs: bool):
    """in_specs for (h_hub, hubs, hub_slot) — dummies ride replicated."""
    if use_hubs:
        return (P(ax, None, None), P(), P(ax, None))
    return (P(), P(), P())


@functools.partial(jax.jit, static_argnames=("mesh", "use_hubs"))
def _probe_impl(fr, h_send, h_valid, h_hub, hubs, hub_slot, *, mesh,
                use_hubs: bool):
    """One sync point: (d, d) per-pair changed-row counts (hub rows
    excluded), global frontier population, and whether any hub row is
    active — everything the host needs to pick the next regime."""
    ax = mesh.axis_names[0]
    d = int(mesh.devices.size)
    n_loc = fr.shape[0] // d

    def shard_body(fr, hs, hv, hh, hubs, hub_slot):
        hs, hv = hs[0], hv[0]
        fr = fr.astype(jnp.bool_)
        sf = hv & fr[hs]
        if use_hubs:
            sf = sf & ~hh[0]
            lo = jax.lax.axis_index(ax).astype(jnp.int32) * n_loc
            owned = (hubs >= lo) & (hubs < lo + n_loc)
            hub_fr = owned & fr[jnp.clip(hubs - lo, 0, n_loc - 1)]
            hub_any = jax.lax.psum(hub_fr.any().astype(jnp.int32), ax) > 0
        else:
            hub_any = jnp.bool_(False)
        cnt = sf.sum(axis=1, dtype=jnp.int32)
        front = jax.lax.psum(fr.sum().astype(jnp.int32), ax)
        return cnt[None, :], front, hub_any

    sm = shard_map(shard_body, mesh=mesh, check_vma=False,
                   in_specs=(P(ax), P(ax, None, None), P(ax, None, None))
                   + _hub_specs(ax, use_hubs),
                   out_specs=(P(ax, None), P(), P()))
    return sm(fr, h_send, h_valid, h_hub, hubs, hub_slot)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "max_iters", "monoid", "plane_repr", "k", "kind", "cap", "lo",
    "use_hubs"))
def _regime_impl(x, fr, live, it0, e_slot, e_recv, e_gid, e_valid, e_start,
                 e_tail, h_send, h_valid, h_hub, hubs, hub_slot, *, mesh,
                 max_iters: int, monoid: str, plane_repr: str, k: int,
                 kind: str, cap: int, lo: int, use_hubs: bool):
    """One transport regime of the sparse fixpoint: a shard_map while-loop
    whose condition is ``alive & it < max_iters & regime-still-applies``.
    Returns the advanced (x, fr, it) plus the per-pair activity counters
    and the measures the host needs to pick the next regime."""
    ax = mesh.axis_names[0]
    d = int(mesh.devices.size)
    n_loc = x.shape[0] // d
    kf = x.shape[1]
    H = h_send.shape[2]
    n_comb = n_loc + d * H
    if monoid == "min":
        ident = jnp.int32(_INT_MAX)
    elif plane_repr == "packed":
        ident = jnp.uint32(0)
    else:
        ident = jnp.zeros((), x.dtype)

    def shard_body(x, fr, live, it0, e_slot, e_recv, e_gid, e_valid,
                   e_start, e_tail, hs, hv, hh, hubs, hub_slot):
        e_slot, e_recv, e_gid, e_valid, e_start, e_tail = (
            a[0] for a in (e_slot, e_recv, e_gid, e_valid, e_start, e_tail))
        hs, hv = hs[0], hv[0]
        has_halo = hv.any(axis=1)                       # (d,)
        row0 = jax.lax.axis_index(ax).astype(jnp.int32) * n_loc
        if use_hubs:
            hh_loc = hh[0]
            owned = (hubs >= row0) & (hubs < row0 + n_loc)
            hub_loc = jnp.clip(hubs - row0, 0, n_loc - 1)
            my_hub_slot = hub_slot[0]
        if plane_repr == "packed" and monoid == "or":
            mask = bitset.pad_mask(k)

        def measures(fr):
            sf = hv & fr[hs]                            # (d, H)
            if use_hubs:
                sfc = sf & ~hh_loc
                hub_fr = owned & fr[hub_loc]
                hub_any = jax.lax.psum(
                    hub_fr.any().astype(jnp.int32), ax) > 0
            else:
                sfc = sf
                hub_fr = None
                hub_any = jnp.bool_(False)
            cnt = sfc.sum(axis=1, dtype=jnp.int32)      # (d,)
            cmax = jax.lax.pmax(cnt.max(), ax)
            return sf, sfc, cnt, cmax, hub_fr, hub_any

        def fits(cmax, hub_any):
            if kind == "dense":
                if cap == 0:                    # no sparse shapes at all
                    return jnp.bool_(True)
                return cmax > cap
            if kind == "sparse":
                upper = cmax <= cap
                if lo == 0:
                    return upper & ((cmax > 0) | hub_any)
                return upper & (cmax > lo)
            return (cmax == 0) & ~hub_any       # local

        def reduce_round(x, comb, frc):
            active = frc[e_slot] & live[e_gid] & e_valid
            if monoid == "min":
                vals = jnp.where(active[:, None], comb[e_slot], _INT_MAX)
                agg = jax.ops.segment_min(vals, e_recv,
                                          num_segments=n_loc)
                new = jnp.minimum(x, agg)
            elif plane_repr == "packed":
                vals = jnp.where(active[:, None], comb[e_slot],
                                 jnp.uint32(0))
                agg = bitset.segment_or_flags(vals, e_start, e_tail,
                                              e_recv, n_loc)
                new = (x | agg) & mask
            else:
                contrib = comb[e_slot] * active[:, None].astype(x.dtype)
                agg = jax.ops.segment_max(contrib, e_recv,
                                          num_segments=n_loc)
                new = jnp.maximum(x, agg)
            return new, jnp.any(new != x, axis=-1)

        def body(state):
            x, fr, it, nonq, quiet = state
            sf, sfc, cnt, _, hub_fr, _ = measures(fr)
            if kind == "dense":
                sr = jnp.where(sf[..., None], x[hs], ident)
                rf = jax.lax.all_to_all(sf, ax, 0, 0)
                rr = jax.lax.all_to_all(sr, ax, 0, 0)
                comb = jnp.concatenate([x, rr.reshape(d * H, kf)], axis=0)
                frc = jnp.concatenate([fr, rf.reshape(d * H)], axis=0)
            else:
                comb = jnp.concatenate(
                    [x, jnp.full((d * H, kf), ident, x.dtype)], axis=0)
                frc = jnp.concatenate(
                    [fr, jnp.zeros((d * H,), jnp.bool_)], axis=0)
                if kind == "sparse":
                    # compact changed rows: (halo-list position, payload)
                    # per pair, capacity `cap`; the loop condition
                    # guarantees every pair fits this round
                    rank = jnp.cumsum(sfc, axis=1) - 1
                    idx = jnp.where(sfc, rank, cap)     # cap => dropped
                    rows2d = jnp.arange(d, dtype=jnp.int32)[:, None]
                    col = jnp.broadcast_to(
                        jnp.arange(H, dtype=jnp.int32)[None, :], (d, H))
                    posb = jnp.full((d, cap), -1, jnp.int32).at[
                        rows2d, idx].set(col, mode="drop")
                    valb = jnp.zeros((d, cap, kf), x.dtype).at[
                        rows2d, idx].set(x[hs], mode="drop")
                    rpos = jax.lax.all_to_all(posb, ax, 0, 0)
                    rval = jax.lax.all_to_all(valb, ax, 0, 0)
                    slot = jnp.where(
                        rpos >= 0,
                        n_loc + rows2d * H + rpos, n_comb).reshape(-1)
                    comb = comb.at[slot].set(rval.reshape(d * cap, kf),
                                             mode="drop")
                    frc = frc.at[slot].set(
                        jnp.ones((d * cap,), jnp.bool_), mode="drop")
                if use_hubs:
                    # broadcast lane: the owner contributes each active
                    # hub row, zeros elsewhere — one psum delivers it
                    # everywhere (exact: every row has a single owner)
                    hrows = jax.lax.psum(
                        jnp.where(hub_fr[:, None], x[hub_loc],
                                  jnp.zeros((), x.dtype)), ax)
                    hflag = jax.lax.psum(hub_fr.astype(jnp.int32), ax) > 0
                    hslot = jnp.where(hflag, my_hub_slot, n_comb)
                    comb = comb.at[hslot].set(hrows, mode="drop")
                    frc = frc.at[hslot].set(
                        jnp.ones(hflag.shape, jnp.bool_), mode="drop")
            new, fr2 = reduce_round(x, comb, frc)
            nonq = nonq + (cnt > 0).astype(jnp.int32)
            quiet = quiet + (has_halo & (cnt == 0)).astype(jnp.int32)
            return new, fr2, it + 1, nonq, quiet

        def cond(state):
            _, fr, it, _, _ = state
            alive = jax.lax.psum(fr.sum().astype(jnp.int32), ax) > 0
            _, _, _, cmax, _, hub_any = measures(fr)
            return alive & (it < max_iters) & fits(cmax, hub_any)

        z = jnp.zeros((d,), jnp.int32)
        x, fr, it, nonq, quiet = jax.lax.while_loop(
            cond, body, (x, fr.astype(jnp.bool_), it0, z, z))
        _, _, cnt, _, _, hub_any = measures(fr)
        front = jax.lax.psum(fr.sum().astype(jnp.int32), ax)
        return (x, fr, it, nonq[None, :], quiet[None, :], cnt[None, :],
                front, hub_any)

    plane_sp = P(ax, None)
    sm = shard_map(
        shard_body, mesh=mesh, check_vma=False,
        in_specs=(plane_sp, P(ax), P(), P(),
                  plane_sp, plane_sp, plane_sp, plane_sp, plane_sp,
                  plane_sp, P(ax, None, None), P(ax, None, None))
        + _hub_specs(ax, use_hubs),
        out_specs=(plane_sp, P(ax), P(), P(ax, None), P(ax, None),
                   P(ax, None), P(), P()))
    return sm(x, fr, live, it0, e_slot, e_recv, e_gid, e_valid, e_start,
              e_tail, h_send, h_valid, h_hub, hubs, hub_slot)


def _pick_regime(cmax: int, hub_any: bool,
                 caps: tuple[int, ...]) -> tuple[str, int, int]:
    """(kind, cap, lo) for the current global changed-row maximum."""
    if cmax == 0 and not hub_any:
        return "local", 0, 0
    for i, c in enumerate(caps):
        if cmax <= c:
            return "sparse", c, (caps[i - 1] if i else 0)
    return "dense", (caps[-1] if caps else 0), 0


def sparse_halo_propagate(plan, x, frontier, live, *, reverse: bool = False,
                          max_iters: int = 256, monoid: str = "or",
                          plane_repr: str = "bool", telemetry=None,
                          caps: tuple[int, ...] | None = None):
    """Sparse twin of ``planes.halo_propagate(halo_mode="dense")`` — same
    (labels, iters) contract including ``iters == max_iters + 1`` on
    truncation, bitwise equal labels, for bool and packed planes under OR
    and int32 planes under MIN.  ``caps`` overrides the automatic
    ``bucket_caps(H)`` capacity schedule (entries >= H are dropped — a
    sparse bucket must be strictly narrower than the dense exchange)."""
    from .planes import PlaneStore
    check_plane_repr(plane_repr)
    if monoid not in ("or", "min"):
        raise ValueError(f"unknown monoid {monoid!r}")
    if monoid == "min" and plane_repr == "packed":
        raise ValueError("plane_repr='packed' supports the OR monoid only")
    dp = plan.bwd if reverse else plan.fwd
    mesh = plan.mesh
    d = int(mesh.devices.size)
    H = dp.h_send.shape[2]
    if caps is None:
        caps = bucket_caps(H)
    else:
        caps = tuple(sorted({int(c) for c in caps if 0 < int(c) < H}))
    use_hubs = plan.hub_count > 0 and dp.hubs is not None
    hub_n = int(dp.hubs.shape[0]) if use_hubs else 0
    if use_hubs:
        h_hub, hubs, hub_slot = dp.h_hub, dp.hubs, dp.hub_slot
    else:
        h_hub = jnp.zeros((1,), jnp.bool_)
        hubs = jnp.zeros((1,), jnp.int32)
        hub_slot = jnp.zeros((1,), jnp.int32)

    k = x.shape[1]
    packed = plane_repr == "packed" and monoid == "or"
    work = PlaneStore.pack_rows(x) if packed else x
    row_bytes = (4 * bitset.n_words(k) if packed
                 else (4 * k if monoid == "min" else k))
    fr = frontier
    it = jnp.zeros((), jnp.int32)

    cnt, front, hub_any = _probe_impl(fr, dp.h_send, dp.h_valid, h_hub,
                                      hubs, hub_slot, mesh=mesh,
                                      use_hubs=use_hubs)
    cnt, front, hub_any = jax.device_get((cnt, front, hub_any))
    alive = int(front) > 0
    while alive and int(it) < max_iters:
        kind, cap, lo = _pick_regime(int(np.max(cnt)), bool(hub_any), caps)
        it_before = int(it)
        work, fr, it, nonq, quiet, cnt, front, hub_any = _regime_impl(
            work, fr, live, it, dp.e_slot, dp.e_recv, dp.e_gid, dp.e_valid,
            dp.e_start, dp.e_tail, dp.h_send, dp.h_valid, h_hub, hubs,
            hub_slot, mesh=mesh, max_iters=max_iters, monoid=monoid,
            plane_repr=plane_repr, k=k, kind=kind, cap=cap, lo=lo,
            use_hubs=use_hubs)
        it_host, nonq, quiet, cnt, front, hub_any = jax.device_get(
            (it, nonq, quiet, cnt, front, hub_any))
        if telemetry is not None:
            telemetry.note_regime(
                kind, int(it_host) - it_before, cap,
                int(np.sum(nonq)), int(np.sum(quiet)),
                d=d, H=H, hub_n=hub_n, row_bytes=row_bytes)
        alive = int(front) > 0
        it = jnp.asarray(it_host, jnp.int32)
    iters = int(it)
    if alive and iters >= max_iters:
        iters = max_iters + 1
    if telemetry is not None:
        telemetry.fixpoints += 1
    out = PlaneStore.unpack_rows(work, k, x.dtype) if packed else work
    return out, jnp.asarray(iters, jnp.int32)

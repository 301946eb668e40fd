"""The general traffic generator: a mix file's parameters in, a whole
schedule out, made from the seed before any window opens.

Two loops, as the mix's ``loop`` says:

- ``"closed"``: one client repeats the mix's ``cycle`` (a list of steps,
  each ``"batch"``, ``"insert"``, ``"delete"`` or ``"rebuild"``; by
  default one batch) until ``max_batches`` batches are drawn.  A batch is
  ``batch`` pairs it sends and waits for; an update is a call it waits
  for between batches: ``insert_batch`` new edges, ``delete_batch`` live
  edges, or ``rebuild()``.  ``warm_cycles`` cycles' updates run in set-up.
- ``"open"``: queries arrive one by one at ``query_rate`` per second; an
  arrival joins the next micro-batch (at most ``batch_cap`` pairs).  Insert
  batches of ``insert_batch`` new edges arrive at ``insert_rate`` per
  second; delete batches of ``delete_batch`` live edges at ``delete_rate``
  per second, each followed ``dirty_s`` seconds later by a rebuild.

Every seed gets the same amount of work: each stream's count is its rate
times the window, and its due times are that many sorted uniform draws (a
Poisson process given its count).  Delete due times keep ``dirty_s`` apart,
so a rebuild always comes due before the next delete.  Pairs are half
uniform and half ``(u, w)`` with ``w`` the end of a random walk of 1 to
``walk_max_len`` steps from ``u`` over the graph live at the arrival, so
that positives occur.  Everything runs in due order, so the graph an item
sees is fixed by the schedule: the generator replays the updates on an
``EdgeLog`` and tags each item with the version it sees.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from itertools import groupby

import numpy as np

from bench.reference import EdgeLog, walk_targets

LOOPS = ("closed", "open")
UPDATES = ("insert", "delete", "rebuild")


@dataclass
class Op:
    """One update of a schedule (or of the set-up)."""
    kind: str                 # "insert" | "delete" | "rebuild"
    due: float                # open loop: seconds after the window opens
    src: np.ndarray | None = None
    dst: np.ndarray | None = None
    version: int = 0          # the EdgeLog version this op produces
    after: int = 0            # closed loop: batches answered before it runs


@dataclass
class Schedule:
    loop: str
    u: np.ndarray             # (Q,) int32 query sources, in due order
    v: np.ndarray             # (Q,) int32 query targets
    due: np.ndarray | None    # (Q,) float64 due times (open loop)
    version: np.ndarray       # (Q,) int64 EdgeLog version each query sees
    batch: int                # closed: pairs per batch; open: micro-batch cap
    ops: list = field(default_factory=list)       # window updates, in order
    warm_ops: list = field(default_factory=list)  # updates run in set-up
    warm_u: np.ndarray | None = None              # one batch for set-up
    warm_v: np.ndarray | None = None


def _pairs(log: EdgeLog, count: int, rng, walk_share: float, max_len: int):
    """``count`` query pairs over the graph live now: a ``walk_share`` of
    them random-walk ends, the rest uniform, in a random order."""
    n = log.n
    u = rng.integers(0, n, count).astype(np.int32)
    v = rng.integers(0, n, count).astype(np.int32)
    walk = rng.permutation(count) < round(walk_share * count)
    if walk.any():
        v[walk] = walk_targets(*log.out_csr(), u[walk], rng, max_len)
    return u, v


def _new_edges(n: int, size: int, rng):
    s = rng.integers(0, n, size).astype(np.int32)
    d = rng.integers(0, n - 1, size).astype(np.int32)
    return s, (d + (d >= s)).astype(np.int32)       # no self-loops


def _live_pick(log: EdgeLog, size: int, rng):
    src, dst = log.snapshot()
    pick = rng.choice(src.size, size, replace=False)
    return src[pick], dst[pick]


def _due_times(count: int, seconds: float, rng, spacing: float = 0.0):
    """``count`` sorted due times in [0, seconds) at least ``spacing``
    apart: sorted uniforms over the room the spacings leave."""
    room = seconds - count * spacing
    if room <= 0:
        raise ValueError(f"{count} items {spacing} s apart do not fit "
                         f"into {seconds} s")
    return np.sort(rng.random(count)) * room + np.arange(count) * spacing


def _draw(op: Op, mix: dict, log: EdgeLog, rng) -> Op:
    """Draw ``op``'s edges over the graph live now and replay it on
    ``log``."""
    if op.kind == "insert":
        op.src, op.dst = _new_edges(log.n, mix["insert_batch"], rng)
        op.version = log.insert(op.src, op.dst)
    elif op.kind == "delete":
        op.src, op.dst = _live_pick(log, mix["delete_batch"], rng)
        op.version = log.delete(op.src, op.dst)
    else:
        op.version = log.version
    return op


def _closed_steps(mix: dict) -> list:
    """The window's steps of a closed mix: its ``cycle`` repeated until
    ``max_batches`` batches, ending with the last of them."""
    cycle = mix.get("cycle", ["batch"])
    steps = cycle * -(-mix["max_batches"] // cycle.count("batch"))
    last = [i for i, k in enumerate(steps) if k == "batch"][
        mix["max_batches"] - 1]
    return steps[:last + 1]


def _closed(mix: dict, log: EdgeLog, rng, walk) -> Schedule:
    """The closed loop: ``warm_cycles`` cycles' updates for the set-up,
    then ``_closed_steps``, each run of consecutive batches drawn in one go
    over the graph live when it is sent."""
    b, cycle = mix["batch"], mix.get("cycle", ["batch"])
    warm = [_draw(Op(k, 0.0), mix, log, rng)
            for _ in range(mix.get("warm_cycles", 0))
            for k in cycle if k != "batch"]
    wu, wv = _pairs(log, b, rng, *walk)
    us, vs, vers, ops, sent = [], [], [], [], 0
    for kind, run in groupby(_closed_steps(mix)):
        count = len(list(run))
        if kind == "batch":
            u, v = _pairs(log, count * b, rng, *walk)
            us.append(u)
            vs.append(v)
            vers.append(np.full(count * b, log.version, np.int64))
            sent += count
        else:
            ops += [_draw(Op(kind, 0.0, after=sent), mix, log, rng)
                    for _ in range(count)]
    return Schedule("closed", np.concatenate(us), np.concatenate(vs), None,
                    np.concatenate(vers), b, ops, warm, warm_u=wu, warm_v=wv)


def check(mix: dict):
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"mix loop {mix.get('loop')!r} is not one of "
                         f"{LOOPS}")
    cycle = mix.get("cycle", ["batch"])
    bad = set(cycle) - {"batch", *UPDATES}
    if bad or "batch" not in cycle:
        raise ValueError(f"mix cycle {cycle} needs a batch, and its other "
                         f"steps must be among {UPDATES}")


def max_inserted_edges(mix: dict, seconds: float) -> int:
    """Edges a window of ``seconds`` inserts, set-up included: what the
    index's edge capacity must leave room for.  A closed mix inserts the
    same whatever the window's length: every insert of its schedule."""
    if mix["loop"] == "closed":
        inserts = mix.get("warm_cycles", 0) \
            * mix.get("cycle", []).count("insert") \
            + _closed_steps(mix).count("insert")
        return inserts * mix.get("insert_batch", 0)
    if not mix.get("insert_rate"):
        return 0
    warm = mix.get("warm_cycles", 0)
    return (round(mix["insert_rate"] * seconds) + warm) * mix["insert_batch"]


def make(mix: dict, log: EdgeLog, rng, seconds: float) -> Schedule:
    """The whole schedule of one run.  ``log`` holds the initial graph and
    is advanced through the set-up and window updates, in due order."""
    check(mix)
    walk = mix.get("walk_share", 0.5), mix.get("walk_max_len", 8)
    if mix["loop"] == "closed":
        return _closed(mix, log, rng, walk)

    # set-up: the update kinds the window uses, ``warm_cycles`` times each,
    # so every program they run is compiled before the window opens
    warm = []
    for _ in range(mix.get("warm_cycles", 0)):
        if mix.get("insert_rate"):
            warm.append(_draw(Op("insert", 0.0), mix, log, rng))
        if mix.get("delete_rate"):
            warm.append(_draw(Op("delete", 0.0), mix, log, rng))
            warm.append(_draw(Op("rebuild", 0.0), mix, log, rng))
    wu, wv = _pairs(log, mix["batch_cap"], rng, *walk)

    ops = []
    for t in _due_times(round(mix.get("insert_rate", 0) * seconds),
                        seconds, rng):
        ops.append(Op("insert", float(t)))
    dirty_s = mix.get("dirty_s", 0.0)
    for t in _due_times(round(mix.get("delete_rate", 0) * seconds),
                        seconds, rng, spacing=dirty_s):
        ops.append(Op("delete", float(t)))
        ops.append(Op("rebuild", float(t + dirty_s)))
    # a rebuild due at the same time as an insert goes first
    order = {"rebuild": 0, "delete": 1, "insert": 2}
    ops.sort(key=lambda o: (o.due, order[o.kind]))
    q_due = _due_times(round(mix["query_rate"] * seconds), seconds, rng)

    us, vs, vers = [], [], []
    lo = 0
    for op in ops + [None]:
        hi = q_due.size if op is None else \
            int(np.searchsorted(q_due, op.due, side="left"))
        if hi > lo:            # queries due before this op see the graph now
            u, v = _pairs(log, hi - lo, rng, *walk)
            us.append(u)
            vs.append(v)
            vers.append(np.full(hi - lo, log.version, np.int64))
            lo = hi
        if op is None:
            break
        _draw(op, mix, log, rng)
    cat = (lambda xs, dt: np.concatenate(xs) if xs
           else np.zeros(0, dt))
    return Schedule("open", cat(us, np.int32), cat(vs, np.int32), q_due,
                    cat(vers, np.int64), mix["batch_cap"], ops, warm,
                    warm_u=wu, warm_v=wv)

"""Reduce a JAX profiler trace of one window to the numbers the per-layer
metrics read.

What it keys on, as a TPU v5e trace of the served path shows it:

- device planes are named ``/device:TPU:<i>``; on each, the line
  ``XLA Modules`` holds one event per program execution, named
  ``jit_<function>(<fingerprint>)`` (``jit_label_phase``,
  ``jit_coalesced``, ``jit_insert_impl``, and for a rebuild
  ``jit_propagate``, ``jit_reach_mask``, ``jit_delta_plane_state`` and
  many one-op programs), and the line ``XLA Ops`` one event per HLO
  operation, named by its HLO text (``%fusion.3 = u32[4096,2]... ``).  The
  ``dbl_query`` Pallas kernel is the custom call ``%dbl_query_verdicts.N =
  s32[1,<Q>] custom-call(..., s32[<flags>,1,<Q>] ...)``;
- the host plane ``/host:CPU`` holds the benchmark's own spans
  (``jax.profiler.TraceAnnotation``): ``window`` around the whole window,
  and ``submit``, ``flush``, ``poll``, ``insert``, ``delete``,
  ``rebuild`` and ``gen-wait`` around each call into the server or wait
  of the load generator.

Host and device events share one clock in the trace.  Device busy time is
the union of the intervals in which an ``XLA Ops`` operation ran (the
``Async XLA Ops`` line holds copies in flight, not work), inside the
``window`` span, averaged over the device planes.
"""
from __future__ import annotations

import bisect
import gzip
import pathlib
import re

SPANS = ("submit", "flush", "poll", "insert", "delete", "rebuild",
         "gen-wait")
WINDOW = "window"
#: programs that answer queries; the rest of a rebuild span's device time
#: belongs to the rebuild
QUERY_PROGRAMS = ("label_phase", "coalesced")
KERNEL = "dbl_query_verdicts"
#: HLO ops whose trace events span the ops they run (counted once, inside)
CONTAINERS = ("while", "conditional", "call")
_KERNEL_Q = re.compile(r"=\s*s32\[1,(\d+)\]")
_KERNEL_FLAGS = re.compile(r"s32\[(\d+),1,(\d+)\]")


def load(path):
    """ProfileData from an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.suffix == ".gz":
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def program_name(module: str) -> str:
    """``jit_label_phase(1134...)`` -> ``label_phase``."""
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_kind(hlo: str) -> str:
    """``%fusion.13 = s32[...] ...`` -> ``fusion``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name.split(".clone")[0])


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _spans(pd):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS or e.name == WINDOW:
                    spans.append((e.name, int(e.start_ns), int(e.end_ns)))
    return spans


def _device(plane, w0, w1):
    lines = {line.name: line for line in plane.lines}
    mods = sorted((int(e.start_ns), int(e.end_ns), program_name(e.name))
                  for e in lines["XLA Modules"].events
                  if e.end_ns > w0 and e.start_ns < w1)
    starts = [m[0] for m in mods]
    ops = []
    for e in lines["XLA Ops"].events:
        s, t = int(e.start_ns), int(e.end_ns)
        if t <= w0 or s >= w1:
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        ops.append((max(s, w0), min(t, w1), prog, e.name))
    return mods, ops


def _gap_label(gap, spans, span_starts):
    """The host span that overlaps the gap most, else ``loop`` (the
    harness's own bookkeeping between calls)."""
    s, e = gap
    best, label = 0, "loop"
    i = bisect.bisect_right(span_starts, e)
    for name, a, b in spans[max(0, i - 64):i]:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, label = ov, name
    return label


def reduce_profile(pd) -> dict:
    spans = _spans(pd)
    windows = [(a, b) for name, a, b in spans if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    inner = sorted((a, name, b) for name, a, b in spans if name != WINDOW
                   and b > w0 and a < w1)
    inner = [(name, max(a, w0), min(b, w1)) for a, name, b in inner]
    span_starts = [a for _, a, _ in inner]
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")

    busy, programs, kinds, gaps = [], {}, {}, {}
    kernel = {"calls": 0, "seconds": 0.0, "lanes": [], "flags": []}
    rebuild_ns = 0
    rebuilds = [(a, b) for name, a, b in inner if name == "rebuild"]
    for plane in planes:
        mods, ops = _device(plane, w0, w1)
        merged = union((s, e) for s, e, _, _ in ops)
        busy.append(length(merged))
        for s, e, prog in mods:
            s, e = max(s, w0), min(e, w1)
            programs[prog] = programs.get(prog, 0) + (e - s) / 1e9
        for s, e, prog, name in ops:
            kind = op_kind(name)
            if kind not in CONTAINERS:
                key = f"{prog}:{kind}"
                kinds[key] = kinds.get(key, 0) + (e - s) / 1e9
            if name.startswith("%" + KERNEL):
                q = _KERNEL_Q.search(name)
                f = _KERNEL_FLAGS.search(name)
                kernel["calls"] += 1
                kernel["seconds"] += (e - s) / 1e9
                kernel["lanes"].append(int(q.group(1)) if q else None)
                kernel["flags"].append(int(f.group(1)) if f else None)
        rb = [(s, e) for s, e, prog, _ in ops if prog not in QUERY_PROGRAMS]
        for a, b in rebuilds:
            rebuild_ns += length(union(clip(rb, a, b)))
        # idle gaps inside the window, by what the host was doing
        edge = w0
        for s, e in merged + [[w1, w1]]:
            if s > edge:
                label = _gap_label((edge, s), inner, span_starts)
                gaps[label] = gaps.get(label, 0) + (s - edge) / 1e9
            edge = max(edge, e)
    nd = len(planes)
    top = sorted(kinds.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / nd / 1e9,
        "devices": nd,
        "programs": {k: v / nd for k, v in programs.items()},
        "kernel": kernel,
        "rebuild_device_s": rebuild_ns / nd / 1e9,
        "rebuild_spans": len(rebuilds),
        "spans": {name: sum(1 for n, _, _ in inner if n == name)
                  for name in SPANS},
        "idle_s": {k: v / nd for k, v in gaps.items()},
        "breakdown": {"device_ops": [[k, v / nd] for k, v in top],
                      "idle_gaps": [[k, v / nd] for k, v in idle]},
    }


def reduce(path) -> dict:
    return reduce_profile(load(path))

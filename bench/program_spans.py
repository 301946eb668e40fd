"""Reduce the program's own host spans in a profiler trace of one window.

The served path marks its own steps with ``jax.profiler.TraceAnnotation``
spans (``repro.serve.spans``): ``server.*`` around each call of
``ReachabilityServer``, ``engine.*`` around the phases of ``QueryEngine``
and ``index.*`` around a rebuild's steps.  A span whose name holds
``.wait_`` covers work that blocks on the device: a wait for a device
result, or a rebuild step, which reads results back between its host
steps.  They sit on the ``/host:CPU`` plane beside the benchmark's own
spans (``trace_reduce.SPANS``), on the device trace's clock.

``reduce_program(pd)`` gives, inside the ``window`` span:

- ``spans``: per span name, its count, total seconds and self seconds (its
  time less that of the program spans nested in it);
- ``frontend_host_s``: the union of the program's spans less the union of
  its wait spans: host time the front end spends between device results;
- ``idle_program_s``: the device's idle gaps, each labelled by the
  innermost program span around its middle (``-`` where none is open).

A trace of a program without these spans gives an empty ``spans`` and
``None`` for the two sums.  ``trace_reduce.reduce_profile`` does not call
this yet (wiring it in adds a key to what the benchmark prints);
``bench/program_report.py`` runs a cell with it.
"""
from __future__ import annotations

import bisect

from bench import trace_reduce as TR

PREFIXES = ("server.", "engine.", "index.")
WAIT = ".wait_"


def program_spans(pd, w0: int, w1: int) -> list[tuple[str, int, int]]:
    """(name, start_ns, end_ns) of the program's spans that overlap the
    window, clipped to it, sorted by start (outer before inner)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s, t = int(e.start_ns), int(e.end_ns)
                if e.name.startswith(PREFIXES) and t > w0 and s < w1:
                    out.append((e.name, max(s, w0), min(t, w1)))
    return sorted(out, key=lambda x: (x[1], -x[2]))


def _window(pd) -> tuple[int, int]:
    wins = [(int(e.start_ns), int(e.end_ns))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == TR.WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {TR.WINDOW!r} span, found "
                         f"{len(wins)}")
    return wins[0]


def _subtract(a, b) -> int:
    """Length of the union ``a`` less the union ``b`` (both merged)."""
    return TR.length(a) - sum(TR.length(TR.clip(b, s, e)) for s, e in a)


def _self_times(spans) -> dict[str, float]:
    """Per name, seconds not covered by a span nested in it.  The spans of
    one thread nest, so a span's children are the spans that start inside
    it; ``spans`` is sorted outer before inner."""
    self_ns = {}
    stack = []          # [name, start, end, children's ns]

    def close(name, s, e, child):
        self_ns[name] = self_ns.get(name, 0) + (e - s - child)

    for name, s, e in spans:
        while stack and stack[-1][2] <= s:
            close(*stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0])
    while stack:
        close(*stack.pop())
    return {k: v / 1e9 for k, v in self_ns.items()}


def _innermost(spans, starts, t: int) -> str:
    """The innermost program span open at ``t``, else ``-``."""
    i = bisect.bisect_right(starts, t)
    best, label = None, "-"
    for name, s, e in spans[max(0, i - 256):i]:
        if s <= t < e and (best is None or s >= best):
            best, label = s, name
    return label


def reduce_program(pd) -> dict:
    w0, w1 = _window(pd)
    spans = program_spans(pd, w0, w1)
    counts, totals = {}, {}
    for name, s, e in spans:
        counts[name] = counts.get(name, 0) + 1
        totals[name] = totals.get(name, 0) + (e - s) / 1e9
    selfs = _self_times(spans)
    out = {"spans": {k: {"count": counts[k], "total_s": totals[k],
                         "self_s": selfs.get(k, 0.0)} for k in counts},
           "frontend_host_s": None, "idle_program_s": None}
    if not spans:
        return out
    every = TR.union((s, e) for _, s, e in spans)
    waits = TR.union((s, e) for n, s, e in spans if WAIT in n)
    out["frontend_host_s"] = _subtract(every, waits) / 1e9
    starts = [s for _, s, _ in spans]
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if planes:
        idle = {}
        for plane in planes:
            _, ops = TR._device(plane, w0, w1)
            edge = w0
            for s, e in TR.union((s, e) for s, e, _, _ in ops) + [[w1, w1]]:
                if s > edge:
                    label = _innermost(spans, starts, (edge + s) // 2)
                    idle[label] = idle.get(label, 0) + (s - edge) / 1e9
                edge = max(edge, e)
        out["idle_program_s"] = {k: v / len(planes) for k, v in idle.items()}
    return out

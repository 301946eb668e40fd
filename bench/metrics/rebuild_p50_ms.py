"""Median over the window's rebuilds of the wall time of ``rebuild()``,
which returns with the rebuilt labels ready: the stall (host clock)."""
import numpy as np


def read(rec):
    t = [o["end"] - o["start"] for o in rec["ops"] if o["kind"] == "rebuild"]
    return float(np.median(t) * 1e3) if t else None

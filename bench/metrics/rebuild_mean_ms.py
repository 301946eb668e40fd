"""The window's rebuild time over its rebuilds: the mean wall time of
``rebuild()``, its drain included, which returns with the rebuilt labels
ready (host clock).  Every rebuild counts by its share, so a stall in any
one of them or a slower full rebuild shows, where a median of a few calls
fixed by their place in the cycle would step or not move."""
import numpy as np


def read(rec):
    t = [o["end"] - o["start"] for o in rec["ops"] if o["kind"] == "rebuild"]
    return float(np.mean(t) * 1e3) if t else None

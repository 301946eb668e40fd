"""Median over the window's insert batches of the time from the batch's
due time to the return of ``insert()``, which waits for the new labels
(host clock).  In the closed loop an insert is due when the window
reaches it, so this is the call's own time."""
import numpy as np


def read(rec):
    t = [o["end"] - o["due"] for o in rec["ops"] if o["kind"] == "insert"]
    return float(np.median(t) * 1e3) if t else None

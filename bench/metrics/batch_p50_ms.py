"""Median over the window's answered batches, clean and dirty alike, of
the time from the batch's submit to its answers on the host (host
clock)."""
import numpy as np


def read(rec):
    t = rec["batch_s"]
    return float(np.median(t[:, 1] - t[:, 0]) * 1e3) if len(t) else None

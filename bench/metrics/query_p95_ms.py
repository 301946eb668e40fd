"""95th percentile over every query of the window of the time from its
due time to its answer on the host (open loop, host clock)."""
import numpy as np


def read(rec):
    lat = rec["latency_s"]
    return float(np.percentile(lat, 95) * 1e3) if lat.size else None

"""95th percentile of how late the load generator issued each item of the
window (queries and updates): issue time minus due time, host clock."""
import numpy as np


def read(rec):
    late = rec["late_s"]
    return float(np.percentile(late, 95) * 1e3) if late.size else None

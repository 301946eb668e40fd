"""Share of the HBM roofline the ``dbl_query`` verdict kernel reaches: the
bytes its calls need at their traced shapes (``bench.roofline``), at the
chip's peak bandwidth (``bench.peaks``), over the kernel's device time in
the trace.  The kernel is bound by bandwidth, not arithmetic."""
from bench import peaks, roofline


def read(rec):
    tr = rec["trace"]
    k = tr and tr["kernel"]
    if not k or not k["calls"] or None in k["lanes"]:
        return None
    cfg = rec["config"]
    moved = sum(roofline.dbl_query_bytes(q, k=cfg["k"],
                                         k_prime=cfg["k_prime"],
                                         nflags=f if f is not None else 3)
                for q, f in zip(k["lanes"], k["flags"]))
    bw = peaks.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return roofline.roofline_share(moved, k["seconds"], bw)

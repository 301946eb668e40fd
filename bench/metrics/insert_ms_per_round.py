"""Device time of the insert program (``jit_insert_impl``, from the trace)
per relaxation round its fixpoints ran: the mean device time of the
window's insert batches over their mean rounds (``insert_rounds`` per
``insert_rounds_batches``, the inserts synced at flush boundaries)."""


def read(rec):
    tr = rec["trace"]
    s = tr and tr["programs"].get("insert_impl")
    n = sum(o["kind"] == "insert" for o in rec["ops"])
    e = rec["engine"]
    r, nr = e.get("insert_rounds"), e.get("insert_rounds_batches")
    if not (s and n and r and nr):
        return None
    return (s * 1e3 / n) / (r / nr)

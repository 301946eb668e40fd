"""Relaxation rounds of the insert program's four plane fixpoints per
insert batch: the engine's ``insert_rounds`` over its
``insert_rounds_batches``, the inserts whose rounds it has synced (at
flush boundaries, so an insert after the window's last flush counts in
neither)."""


def read(rec):
    e = rec["engine"]
    r, n = e.get("insert_rounds"), e.get("insert_rounds_batches")
    return r / n if r and n else None

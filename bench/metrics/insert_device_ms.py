"""Device time of the engine's insert program (``jit_insert_impl``) per
insert batch of the window, from the trace."""


def read(rec):
    tr = rec["trace"]
    n = sum(o["kind"] == "insert" for o in rec["ops"])
    s = tr and tr["programs"].get("insert_impl")
    return s * 1e3 / n if s and n else None

"""Relaxation rounds of a rebuild's label fixpoints (delta or full): the
engine's ``rebuild_rounds`` over its ``rebuilds``, both counted as each
rebuild ends (replicated layout)."""


def read(rec):
    e = rec["engine"]
    r, n = e.get("rebuild_rounds"), e.get("rebuilds")
    return r / n if r and n else None

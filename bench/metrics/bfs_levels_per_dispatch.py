"""BFS levels per coalesced residue dispatch: the engine's ``bfs_levels``
over its ``bfs_dispatches`` (counts, exact for a seed).  A dispatch runs
as many levels as its slowest lane needs, each level one relaxation of
every edge for every lane."""


def read(rec):
    e = rec["engine"]
    if not e.get("bfs_levels") or not e.get("bfs_dispatches"):
        return None
    return e["bfs_levels"] / e["bfs_dispatches"]

"""Share of the window's queries that rode the BFS residue: the engine's
``prune_hits["bfs"]`` over its query count (a count, exact for a seed)."""


def read(rec):
    e = rec["engine"]
    if not e.get("queries"):
        return None
    return 100.0 * e["prune_hits"]["bfs"] / e["queries"]

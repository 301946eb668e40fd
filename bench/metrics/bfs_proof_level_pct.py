"""Share of BFS levels run after a dispatch's last hit, which only prove
its remaining lanes unreachable (``bfs_proof_levels`` / ``bfs_levels``)."""


def read(rec):
    e = rec["engine"]
    if not e.get("bfs_levels"):
        return None
    return 100.0 * e.get("bfs_proof_levels", 0) / e["bfs_levels"]

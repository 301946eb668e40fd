"""Device time of the coalesced BFS-residue programs (``jit_coalesced``,
from the trace) per BFS level the engine counted (``bfs_levels``): the
cost of one relaxation of every edge for a dispatch's lanes."""


def read(rec):
    tr = rec["trace"]
    s = tr and tr["programs"].get("coalesced")
    levels = rec["engine"].get("bfs_levels")
    return s * 1e3 / levels if s and levels else None

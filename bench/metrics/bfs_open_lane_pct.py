"""Share of the BFS residue's lane-levels that did useful work: the levels
each lane that rode a dispatch stayed open (until its hit, or to the
dispatch's end if it never hit), over every lane slot times the levels
its dispatch ran (``bfs_open_lane_levels`` / ``bfs_slot_levels``)."""


def read(rec):
    e = rec["engine"]
    if not e.get("bfs_slot_levels"):
        return None
    return 100.0 * e.get("bfs_open_lane_levels", 0) / e["bfs_slot_levels"]

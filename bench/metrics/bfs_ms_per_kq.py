"""Device time of the engine's coalesced BFS-residue programs
(``jit_coalesced``) per thousand queries answered, from the trace."""


def read(rec):
    tr = rec["trace"]
    s = tr and tr["programs"].get("coalesced")
    if not s or not rec["queries"]:
        return None
    return s * 1e3 / (rec["queries"] / 1e3)

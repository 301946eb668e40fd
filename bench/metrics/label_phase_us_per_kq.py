"""Device time of the engine's label-phase programs (``jit_label_phase``)
per thousand queries answered, from the trace."""


def read(rec):
    tr = rec["trace"]
    s = tr and tr["programs"].get("label_phase")
    if not s or not rec["queries"]:
        return None
    return s * 1e6 / (rec["queries"] / 1e3)

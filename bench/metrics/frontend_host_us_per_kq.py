"""Host time of the server front end per thousand queries answered: the
union of the program's ``server.*``/``engine.*``/``index.*`` spans less
its ``*.wait_*`` spans (``program_spans.reduce_program``), from the
trace's ``program`` key."""


def read(rec):
    prog = rec["trace"] and rec["trace"].get("program")
    s = prog and prog["frontend_host_s"]
    if s is None or not rec["queries"]:
        return None
    return s * 1e6 / (rec["queries"] / 1e3)

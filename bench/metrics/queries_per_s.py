"""Queries answered in the window over the window's seconds, on the host
clock.  The window runs until the last batch it started is answered, so
all the work and all the time count."""


def read(rec):
    if not rec["queries"]:
        return None
    return rec["queries"] / rec["window_s"]

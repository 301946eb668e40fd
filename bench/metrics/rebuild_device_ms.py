"""Device busy time inside the benchmark's ``rebuild`` spans, less the
query programs that drain there, per rebuild of the window (trace)."""


def read(rec):
    tr = rec["trace"]
    if not tr or not tr["rebuild_spans"]:
        return None
    return tr["rebuild_device_s"] * 1e3 / tr["rebuild_spans"]

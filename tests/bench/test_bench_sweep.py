"""The knee sweep's control flow on the CPU at a tiny size: each stream
steps up its rates until one is not sustained, on one index."""
import benchtiny
from bench import sweep


def test_sweep_finds_a_knee_per_stream(tmp_path):
    root = benchtiny.make_root(tmp_path)
    steps = {"query": ([100, 1_000_000], 0.5), "insert": ([1.0], 1.0),
             "delete": ([0.5], 2.0)}
    out = sweep.sweep("email.churn", 4, backend="pallas-interpret",
                      root=root, steps=steps)
    q = out["steps"]["query"]
    assert q[0]["ok"] and not q[-1]["ok"]
    assert out["knee"] == {"query": 100, "insert": 1.0, "delete": 0.5}

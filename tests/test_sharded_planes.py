"""Vertex-sharded PlaneStore suite (the PR-5 sharded-suite CI step).

The differential assertions live in tests/distributed/run_sharded_planes.py
and run in a subprocess with XLA_FLAGS forcing 4 host devices (the main
test process keeps its single CPU device): the whole sharded lifecycle —
build / insert / delete / delta+full rebuild / sync + pipelined queries —
must be bitwise identical to the replicated oracle, per-device label-plane
bytes must be 1/shards of replicated, the compiled verdict path must
contain no all-gather, and steady-state serving must not grow the
dispatch-shape budget."""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_sharded_planes_differential():
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{ROOT}"
    out = subprocess.run(
        [sys.executable, str(ROOT / "tests/distributed/run_sharded_planes.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "SHARDED_PLANES_OK" in out.stdout


@pytest.mark.slow
def test_sharded_bfs_counts_levels_like_replicated():
    """tests/distributed/run_bfs_counters.py: the sharded coalesced program
    returns the replicated one's hit levels and level count, and a stream
    leaves both engines with the same BFS counters."""
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{ROOT}"
    out = subprocess.run(
        [sys.executable, str(ROOT / "tests/distributed/run_bfs_counters.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "BFS_COUNTERS_OK" in out.stdout

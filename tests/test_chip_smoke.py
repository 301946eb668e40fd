"""chip_smoke.py on the CPU: it refuses to run without a TPU, its host BFS
reference is right, and its phases pass at a tiny size with the kernels in
the Pallas interpreter (one device) and on forced host devices (four)."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke as C
from tests.conftest import random_graph, reach_oracle

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(n=2000, m=4200, seed=3, rounds=2, batch=1024, insert_batch=64,
            delete_batch=64, dirty_batch=512, checks=64)


def test_main_refuses_without_a_tpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this host has a TPU")
    assert C.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and out.strip() == ""


def test_host_reach_matches_the_closure_oracle():
    rng = np.random.default_rng(5)
    for _ in range(20):
        n, src, dst = random_graph(rng, n_max=40, m_max=120)
        R = reach_oracle(n, src, dst)
        us = rng.integers(0, n, 150).astype(np.int32)
        ws = rng.integers(0, n, 150).astype(np.int32)
        np.testing.assert_array_equal(C.host_reach(src, dst, n, us, ws),
                                      R[us, ws])


def test_walk_targets_are_reachable():
    rng = np.random.default_rng(6)
    n, src, dst = random_graph(rng, n_max=40, m_max=120)
    us = rng.integers(0, n, 200).astype(np.int32)
    ws = C.walk_targets(src, dst, n, us, rng)
    assert reach_oracle(n, src, dst)[us, ws].all()


def test_smoke_phases_one_device_interpret():
    stats = C.smoke_one_chip(backend="pallas-interpret", **TINY)
    hits = stats["prune_hits"]
    assert min(hits["dl"], hits["bl"], hits["bfs"]) > 0
    assert stats["rebuilds"] == 1 and not stats["dirty"]
    assert stats["queries"] == TINY["rounds"] * TINY["batch"] \
        + TINY["dirty_batch"] + TINY["batch"]


def test_smoke_phases_four_forced_devices():
    """The --chips 4 path (vertex-sharded beside replicated) on four forced
    host devices, in a subprocess: the device count is fixed at start-up."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT / 'src'}:{ROOT}")
    code = ("import json, chip_smoke as C; "
            f"C.smoke_four_chips(**json.loads({json.dumps(json.dumps(TINY))}))"
            "; print('FOUR_OK')")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-4000:]
    assert "FOUR_OK" in out.stdout
    assert "each on 4 distinct devices" in out.stdout

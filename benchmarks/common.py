"""Shared benchmark infrastructure.

Graphs are synthetic with Table 2-matched statistics (SNAP datasets are not
redistributable offline).  ``--scale`` shrinks every preset proportionally;
timing medians of N repeats after a warmup.  These benches time whatever
backend JAX runs on: on a CPU they measure XLA's CPU backend and the Pallas
interpreter, not the chip, so their numbers compare DBL with its baselines
on the same host and say nothing about TPU speed.  ``chip_smoke.py`` is
what runs the served path on a TPU.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from repro.core import DBLIndex, make_graph
from repro.graphs.generators import TABLE2_PRESETS, table2_graph

DEFAULT_DATASETS = ("LJ", "Web", "Email", "Wiki", "Pokec", "BerkStan",
                    "Twitter", "Reddit")


def timed(fn, *, repeats: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


@dataclass
class BenchGraph:
    name: str
    n: int
    src: np.ndarray
    dst: np.ndarray

    def index(self, *, k=64, k_prime=64, m_extra=0, max_iters=64,
              selection="product", leaf_r=0) -> DBLIndex:
        g = make_graph(self.src, self.dst, self.n,
                       m_cap=len(self.src) + m_extra)
        return DBLIndex.build(g, n_cap=self.n, k=k, k_prime=k_prime,
                              max_iters=max_iters, selection=selection,
                              leaf_r=leaf_r)


def load(name: str, *, scale: float = 0.15, seed: int = 0) -> BenchGraph:
    n, src, dst = table2_graph(name, seed=seed, scale=scale)
    return BenchGraph(name, n, src, dst)


def random_queries(bg: BenchGraph, q: int, seed: int = 1):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, bg.n, q).astype(np.int32),
            rng.integers(0, bg.n, q).astype(np.int32))


def csv_row(name: str, us_per_call: float, derived: str = "") -> str:
    return f"{name},{us_per_call:.3f},{derived}"

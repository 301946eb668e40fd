"""Paper Fig 6 / Table 7: parallel query processing.

CPU-sequential vs CPU-vectorized (batch lanes) vs the Pallas fast-path
kernel: compiled on a TPU, in the Pallas interpreter elsewhere (the
``kernel_mode`` column says which; an interpreter time is not a kernel
time).  The scaling axis on TPU is the query batch per step — the
vertex-centric thread scaling of the paper maps to data-parallel lanes.
B-BFS is the no-index baseline.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.baselines import bbfs
from repro.core import query as Q
from repro.kernels.dbl_query.ops import query_verdicts
from .common import csv_row, load, random_queries, timed


def main(scale: float = 0.1, n_queries: int = 50_000,
         datasets=("LJ", "Email", "Wiki", "Reddit")):
    rows = []
    interpret = jax.default_backend() != "tpu"
    mode = "interpret" if interpret else "compiled"
    print("dataset,batch,kernel_mode,label_path_ms,kernel_path_ms,"
          "bbfs_ms_per_1k")
    for name in datasets:
        bg = load(name, scale=scale)
        idx = bg.index()
        u, v = random_queries(bg, n_queries)
        uj, vj = jnp.asarray(u), jnp.asarray(v)

        for batch in (1_000, 10_000, n_queries):
            ub, vb = uj[:batch], vj[:batch]
            t_label = timed(lambda: Q.label_verdicts(
                idx.packed, ub, vb).block_until_ready())
            t_kernel = timed(lambda: query_verdicts(
                idx.packed, ub, vb, q_block=512,
                interpret=interpret).block_until_ready())
            rows.append((name, batch, mode, t_label, t_kernel))
            print(f"{name},{batch},{mode},{1e3 * t_label:.2f},"
                  f"{1e3 * t_kernel:.2f},", end="")
            if batch == 1_000:
                t_bbfs = timed(lambda: bbfs.query(
                    idx.graph, u[:1000], v[:1000], n_cap=bg.n, chunk=64,
                    max_iters=64), repeats=1)
                print(f"{1e3 * t_bbfs:.1f}")
            else:
                print("")
    return rows


if __name__ == "__main__":
    main()

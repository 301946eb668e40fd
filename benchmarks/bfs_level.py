"""Cost of one BFS-residue level, push form against pull form, on the chip.

    PYTHONPATH=src python benchmarks/bfs_level.py [--scale 1.0] [--lanes 256]

Builds the Wiki deployment's graph (``dag_like`` at 1.992M vertices and
4.15M edges, fixed seed 2101, 5% back edges, scaled by ``--scale``), its
in-edge layout (``core.pull``, timed once compiled), and times
``core.query.pruned_bfs`` with the engine's int8 frontier, with and without
the layout, at two loop lengths.  The lanes start at random vertices and
target vertices of in-degree 0, and every vertex is admitted, so no lane
ever hits and each call runs exactly ``max_iters`` levels: the difference
of the two lengths' times over their difference in levels is the cost of
one level, and what is left is the call's fixed cost (the pull's layout
permutation among it).
Times are the median of ``--reps`` calls, each ended by
``block_until_ready``, after one call that compiles.  The last line of
standard output is one JSON object; without a TPU the script refuses,
unless ``--rehearse`` (a CPU run at a tiny ``--scale``).
"""
import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pull as P
from repro.core import query as Q
from repro.core.graph import make_graph
from repro.graphs.generators import dag_like

N_WIKI, M_WIKI, SEED, BACK = 1_992_000, 4_150_000, 2101, 0.05


def timed(fn, reps):
    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t)
    return statistics.median(ts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--lanes", type=int, default=256)
    ap.add_argument("--levels", default="2,10")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse:
        print("bfs_level: JAX finds no TPU; nothing was run", file=sys.stderr)
        return 2
    n, m = int(N_WIKI * a.scale), int(M_WIKI * a.scale)
    src, dst = dag_like(n, m, seed=SEED, back_frac=BACK)
    m_cap = -(-m // 4096) * 4096
    g = make_graph(src, dst, n, m_cap=m_cap)
    build_s = timed(lambda: P.build(g, m, n)[0], 1)   # after its compile
    lay = P.without_tail(P.build(g, m, n)[0])   # nothing inserted since
    slots = sum(int(np.prod(s.shape)) for s in lay.src)

    rng = np.random.default_rng(0)
    deg = np.bincount(dst, minlength=n)
    u = jnp.asarray(rng.integers(0, n, a.lanes), jnp.int32)
    v = jnp.asarray(rng.choice(np.flatnonzero(deg == 0), a.lanes), jnp.int32)
    admit = jnp.ones((n, a.lanes), jnp.int8)
    cut = jnp.full((a.lanes,), Q.FRESH_CUT, jnp.int32)
    zeros = jnp.zeros((8, 2), jnp.uint32)
    p = Q.PackedLabels(zeros, zeros, zeros, zeros)

    def call(levels, layout):
        return lambda: Q.pruned_bfs(g, p, u, v, admit, cut, n_cap=n,
                                    max_iters=levels, counted=True,
                                    layout=layout)

    lo, hi = (int(x) for x in a.levels.split(","))
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "n": n, "m": m, "lanes": a.lanes,
           "layout": {"build_s": build_s,
                      "classes": [list(s.shape) for s in lay.src],
                      "rows": int(lay.inv.shape[0]), "slots": slots,
                      "pad_share": slots / m - 1,
                      "in_degree_0": int((deg == 0).sum()),
                      "max_in_degree": int(deg.max())}}
    for name, layout in (("push", None), ("pull", lay)):
        t_lo = timed(call(lo, layout), a.reps)
        t_hi = timed(call(hi, layout), a.reps)
        levels = int(np.asarray(call(hi, layout)())[:, 1].max())
        per = (t_hi - t_lo) / (hi - lo)
        out[name] = {"ms_per_level": per * 1e3,
                     "fixed_ms": (t_lo - lo * per) * 1e3,
                     f"s_{lo}": t_lo, f"s_{hi}": t_hi, "levels_run": levels}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Where a traced run's time went, by the program's own spans and by named
scope, with the readings of the metrics no cell lists yet.

    python bench/program_report.py --workload <name> --seed <n>
        --seconds <s> [--out <report.json>] [--keep-trace <file.gz>]

runs the cell on the chip as ``bench/run.py --trace 1`` does
(``harness.run_cell``, the same set-up, window and check) and prints, as
the last line of standard output, one JSON object:

- ``result``: what ``bench/run.py`` would print;
- ``program``: ``program_spans.reduce_program`` of the run's trace (count,
  total and self seconds of each program span, ``frontend_host_s``,
  ``idle_program_s``);
- ``scopes``: the device seconds of the ``coalesced`` BFS-residue program
  by named scope (``bfs.gather``, ``bfs.reduce``, ``bfs.update``,
  ``bfs.recheck``; ``while.cond`` for the level loop's condition, ``-``
  for ops under none) and the events and seconds of its costliest ops.
  The chip's ``XLA Ops`` events name each HLO instruction but carry no
  ``op_name``, so the scopes come from the compiled HLO of the engine's
  ``coalesced`` program at its full chunk, compiled once more after the
  window.  Ops of a smaller chunk are mapped by the same names;
- ``readings``: the readers of ``READERS`` on the run's record;
- ``engine``: the engine's counters over the window.

``--load <trace.xplane.pb[.gz]>`` reduces a saved trace instead: its
``program`` key alone, since the scopes need the program compiled.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import program_spans as PS  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402

#: readers in ``bench/metrics/`` that no entry of ``BENCHMARK.json`` lists
READERS = ("bfs_levels_per_dispatch", "bfs_ms_per_level",
           "bfs_open_lane_pct", "bfs_proof_level_pct",
           "frontend_host_us_per_kq", "insert_rounds_per_batch",
           "insert_ms_per_round", "rebuild_rounds_per_rebuild")
PROGRAM = "coalesced"
_INSTR = re.compile(r'^\s*(?:ROOT )?(%[\w.\-]+) = .*\bop_name="([^"]*)"')
_SCOPE = re.compile(r"\b(bfs\.[a-z]+|label\.[a-z]+)\b")


def hlo_scopes(hlo: str) -> dict[str, str]:
    """HLO instruction name -> the named scope in its ``op_name``
    (``%fusion.3`` -> ``bfs.reduce``), from a compiled module's text."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            scope = _SCOPE.search(m.group(2))
            out[m.group(1)] = (scope.group(1) if scope else
                               "while.cond" if "/cond/" in m.group(2)
                               else "-")
    return out


def time_by_scope(pd, scopes: dict[str, str], program: str = PROGRAM,
                  top: int = 12) -> dict:
    """Device seconds of ``program``'s ops inside the window by named
    scope (instructions ``scopes`` does not name count under ``-``), and
    the ``top`` costliest ops: [instruction, scope, events, seconds].
    Container ops (a ``while``) are left out: the ops they run count."""
    w0, w1 = PS._window(pd)
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    by_scope, ops = {}, {}
    for plane in planes:
        for s, e, prog, name in TR._device(plane, w0, w1)[1]:
            if prog != program or TR.op_kind(name) in TR.CONTAINERS:
                continue
            key = name.split(" = ", 1)[0]
            scope = scopes.get(key, "-")
            by_scope[scope] = by_scope.get(scope, 0) + (e - s) / 1e9
            n, sec = ops.get(key, (0, 0.0))
            ops[key] = (n + 1, sec + (e - s) / 1e9)
    nd = max(len(planes), 1)
    costly = sorted(ops.items(), key=lambda kv: -kv[1][1])[:top]
    return {"s": {k: v / nd for k, v in by_scope.items()},
            "ops": [[k, scopes.get(k, "-"), n // nd, sec / nd]
                    for k, (n, sec) in costly]}


def coalesced_hlo(engine, chunk: int | None = None) -> str:
    """Compiled HLO text of the engine's ``coalesced`` program for a
    ``chunk``-lane dispatch (its full chunk by default) on its bound
    index, with the operands a flush gives it."""
    import jax.numpy as jnp
    from repro.core import query as Q
    chunk = chunk or engine.bfs_chunk
    idx = engine.index
    n_cap = idx.packed.dl_in.shape[0]
    fn = engine._coal_phases[chunk]
    return fn.lower(idx.graph, idx.packed, idx.il,
                    jnp.full((chunk,), n_cap, jnp.int32),
                    jnp.zeros((chunk,), jnp.int32),
                    jnp.full((chunk,), Q.FRESH_CUT, jnp.int32),
                    jnp.asarray(idx.dirty_flag),
                    *engine._coalesced_extra_args()).compile().as_text()


def run(workload: str, seed: int, seconds: float, *, backend: str = "pallas",
        keep_trace: str | None = None, t_process: float | None = None,
        **run_kw) -> dict:
    """One traced run of ``workload`` with the report's additions.  The
    harness is entered at three names it looks up at call time: its
    ``set_up`` (to keep the engine), ``trace_reduce.reduce`` (to reduce
    the program's spans and scopes from the same trace) and its
    ``read_metric`` (to keep the run's record)."""
    from bench import harness as H
    kept = {}
    real_set_up, real_reduce, real_read = H.set_up, TR.reduce, H.read_metric

    def set_up(*a, **kw):
        server, sched, edge_log = real_set_up(*a, **kw)
        kept["engine"] = server.engine
        return server, sched, edge_log

    def reduce(path):
        if keep_trace:
            with open(path, "rb") as f, gzip.open(keep_trace, "wb") as g:
                shutil.copyfileobj(f, g)
        pd = TR.load(path)
        out = TR.reduce_profile(pd)
        kept["program"] = PS.reduce_program(pd)
        kept["scopes"] = time_by_scope(
            pd, hlo_scopes(coalesced_hlo(kept["engine"])))
        return out

    def read_metric(name, rec, root=H.ROOT):
        kept["rec"] = rec
        return real_read(name, rec, root)

    H.set_up, TR.reduce, H.read_metric = set_up, reduce, read_metric
    try:
        result = H.run_cell(workload, seed, seconds, trace=True,
                            backend=backend, t_process=t_process, **run_kw)
    finally:
        H.set_up, TR.reduce, H.read_metric = (real_set_up, real_reduce,
                                              real_read)
    rec = kept["rec"]
    rec["trace"] = dict(rec["trace"], program=kept["program"])
    readings = {n: real_read(n, rec) for n in READERS}
    return {"result": result, "program": kept["program"],
            "scopes": kept["scopes"],
            "readings": {k: v for k, v in readings.items() if v is not None},
            "engine": rec["engine"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--out", help="also write the report here")
    ap.add_argument("--keep-trace", help="save the run's trace (gzipped)")
    ap.add_argument("--load", help="reduce this saved trace instead")
    a = ap.parse_args(argv)

    if a.load:
        report = {"program": PS.reduce_program(TR.load(a.load))}
    else:
        if a.workload is None or a.seed is None or a.seconds is None:
            ap.error("--workload, --seed and --seconds, or --load")
        import jax
        if jax.devices()[0].platform != "tpu":
            print("program_report: JAX finds no TPU; nothing was run",
                  file=sys.stderr)
            return 3
        from repro.serve.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        report = run(a.workload, a.seed, a.seconds, keep_trace=a.keep_trace,
                     t_process=T_PROCESS)
    line = json.dumps(report)
    if a.out:
        pathlib.Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(a.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Find the highest rates the served path sustains for an open-loop mix,
one kind of traffic at a time, in one process with one set-up.

    python bench/sweep.py --workload email.steady --seed 5

For each of the mix's streams alone (queries; insert batches; delete
cycles of delete, ``dirty_s`` of dirty serving and a rebuild) it runs the
mix's own generator and window at rising rates, a step of a few seconds
each, on one index that carries the updates forward.  A rate is sustained
when the backlog does not grow over its step: the lateness of the items
(issue time minus due time) has a slope below ``MAX_SLOPE`` seconds per
second of due time, and the step's last answer comes within ``DRAIN_S`` of
its close.  The knee of a stream is the highest sustained rate below the
first one that is not.  The last line of standard output is the whole
result as one JSON object: every step and each stream's knee.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: rates tried per stream (per second), and seconds per step
STEPS = {
    "query": ([2000, 4000, 6000, 8000, 12000, 16000, 24000, 32000, 48000,
               64000, 96000, 128000], 6.0),
    "insert": ([0.5, 0.75, 1.0, 1.25, 1.5, 1.75, 2.0, 2.5, 3.0], 10.0),
    "delete": ([0.1, 0.15, 0.2, 0.25, 0.3, 0.4, 0.5, 0.6], 30.0),
}
MAX_SLOPE = 0.05
DRAIN_S = 1.0
#: delete cycles made during set-up, so every program is compiled
WARM_CYCLES = 3


def alone(mix: dict, stream: str, rate: float) -> dict:
    m = dict(mix, insert_rate=0, delete_rate=0, query_rate=0, warm_cycles=0)
    m[f"{stream}_rate"] = rate
    return m


def sustained(win, step_s: float) -> dict:
    import numpy as np
    late, due = np.asarray(win.late), np.asarray(win.late_due)
    slope = float(np.polyfit(due, late, 1)[0]) if due.size >= 3 else 0.0
    drain = win.end - step_s
    return {"slope": slope, "drain_s": drain,
            "late_p95_ms": float(np.percentile(late, 95) * 1e3),
            "items": int(late.size),
            "ok": slope < MAX_SLOPE and drain < DRAIN_S}


def sweep(workload: str, seed: int, *, backend: str = "pallas",
          root: pathlib.Path = ROOT, steps: dict = STEPS) -> dict:
    """Every stream's steps and knee; ``backend`` and ``root`` as in
    ``harness.run_cell``."""
    import numpy as np
    from bench import harness as H

    w, cfg, mix = H.find_cell(H.benchmark(root), workload, root)
    gen = H.generator(mix, root)
    warm_mix = dict(mix, warm_cycles=WARM_CYCLES)
    # room for the set-up's inserts and every insert step's
    ins_rates, ins_s = steps.get("insert", ([], 0.0))
    room = gen.max_inserted_edges(warm_mix, 1.0) + mix["insert_batch"] * sum(
        round(r * ins_s) for r in ins_rates)
    # the same set-up as a cell's run, with room for the sweep's inserts
    server, warm, log = H.set_up(cfg, warm_mix, gen, seed, 1.0, backend,
                                 edge_room=room, root=root)
    rng = H.rng_for(seed, 3)
    # the warm schedule's own window items: run them, so the log and the
    # server agree before the first step
    H.Window(server, warm, 1.0, H._no_span).run(
        version=sum(o.kind != "rebuild" for o in warm.warm_ops))
    H.log(f"set-up {time.perf_counter() - T_PROCESS:.1f} s")

    out = {"workload": workload, "seed": seed, "steps": {}, "knee": {}}
    for stream, (rates, step_s) in steps.items():
        rows = []
        for rate in rates:
            m = alone(mix, stream, rate)
            if stream == "delete" and rate * m["dirty_s"] >= 1:
                break
            version = log.version
            sched = gen.make(m, log, rng, step_s)
            win = H.Window(server, sched, step_s, H._no_span)
            win.end = win.run(version=version)
            row = dict(rate=rate, **sustained(win, step_s))
            for kind in ("insert", "rebuild"):
                took = [o["end"] - o["start"] for o in win.ops
                        if o["kind"] == kind]
                if took:
                    row[f"{kind}_s_p50"] = float(np.median(took))
            rows.append(row)
            H.log(f"sweep {stream} {json.dumps(row)}")
            if not row["ok"]:
                break
        out["steps"][stream] = rows
        ok = [r["rate"] for r in rows if r["ok"]]
        out["knee"][stream] = ok[-1] if ok else None
    H.log(f"sweep total {time.perf_counter() - T_PROCESS:.1f} s")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    a = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX finds no TPU; nothing was run", file=sys.stderr)
        return 3
    from repro.serve.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(json.dumps(sweep(a.workload, a.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

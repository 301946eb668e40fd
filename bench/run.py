"""Run one benchmark cell on the chip and print its result.

    python bench/run.py --workload <name> --seed <n> --seconds <s>
        --trace <0|1>

Runs from the root of a checkout.  The cell (configuration and traffic mix)
is found by name in ``BENCHMARK.json``; ``bench/harness.py`` says what a
run does.  Lines before the last are diagnostics; the last line of standard
output is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``) and, last,
``checks``: each number compared with its limit.  The same numbers are the
last lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    from bench import harness as H
    bm = H.benchmark()
    w, _, _ = H.find_cell(bm, a.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (platform {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 3
    if len(devices) < w["chips"]:
        print(f"bench: {a.workload} needs {w['chips']} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 3
    from bench import peaks
    peaks.peaks(devices[0].device_kind)      # an unknown chip is an error
    from repro.serve.compile_cache import enable_compile_cache
    H.log("compile cache:", enable_compile_cache())
    # cache every program, however quick to compile, so that a warm run's
    # set-up loads instead of compiling
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    H.log(f"device: {devices[0].device_kind} x {len(devices)}; "
          f"workload {a.workload}, seed {a.seed}, {a.seconds:g} s, "
          f"trace {a.trace}")
    out = H.run_cell(a.workload, a.seed, a.seconds, trace=bool(a.trace),
                     backend="pallas", t_process=T_PROCESS,
                     devices=devices[:w["chips"]])
    H.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run a cell's control: the served path with the one guarantee the
configuration names under ``control`` broken, through the program's own
switch for it.  Its runs must come out not correct, which shows that the
comparison deciding ``correct`` can fail.

    python bench/control.py --workload <name> --seconds <s> --seeds 1 2 3

Runs each seed in turn in one process, at the cell's own size and load,
and prints one JSON line per seed: ``correct`` and the numbers compared.
The benchmark's own runs never run it.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    import jax
    from bench import harness as H
    if jax.devices()[0].platform != "tpu":
        print("control: JAX finds no TPU; nothing was run", file=sys.stderr)
        return 3
    from repro.serve.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in a.seeds:
        out = H.run_cell(a.workload, seed, a.seconds, trace=False,
                         control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

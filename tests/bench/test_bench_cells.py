"""Each cell's traffic end to end at a tiny size on the CPU, with the
kernels in the Pallas interpreter: the result line has its keys, the
cell's metrics and ``correct``; the command refuses a host with no TPU;
a configuration, a mix and a metric dropped in as new files are found by
name; the schedule is a function of the seed alone."""
import json

import numpy as np
import pytest

import benchtiny
from bench import harness as H
from bench import reference as R

SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_run_refuses_without_a_tpu(capsys):
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this host has a TPU")
    from bench import run
    assert run.main(["--workload", benchtiny.cells()[0], "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell", benchtiny.cells())
def test_cell_runs_end_to_end(cell, tmp_path):
    root = benchtiny.make_root(tmp_path)
    out = H.run_cell(cell, SEED, 1.5, trace=False,
                     backend="pallas-interpret", root=root)
    assert list(out) == KEYS
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in H.cell_metrics(H.benchmark(root), cell,
                                              trace=False)}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == 1
    assert set(out["checks"]) == {"wrong_answers", "unanswered"}


def test_new_files_are_found_by_name(tmp_path):
    """A new configuration, mix and metric, and the entries naming them,
    run without an edit to any file the benchmark already has."""
    root = benchtiny.make_root(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "email-euall.json").read_text())
    (b / "configs" / "tiny-new.json").write_text(
        json.dumps(dict(cfg, name="tiny-new", n=2000, m=3000)))
    mix = json.loads((b / "traffic" / "bulk-walk.json").read_text())
    (b / "traffic" / "pairs-new.json").write_text(
        json.dumps(dict(mix, walk_share=0.0, batch=1024)))
    (b / "metrics" / "answered_total.py").write_text(
        "def read(rec):\n    return float(rec['queries'])\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "tiny.new", "config": "tiny-new",
                            "traffic": "pairs-new", "chips": 1,
                            "why": "test"})
    bm["end_to_end"].append({"name": "answered_total", "unit": "queries",
                             "better": "higher", "bound": 0.01,
                             "source": "host_clock",
                             "workloads": ["tiny.new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    out = H.run_cell("tiny.new", 3, 1.0, trace=False,
                     backend="pallas-interpret", root=root)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"answered_total", "setup_s"}
    assert out["metrics"]["answered_total"]["value"] > 0


@pytest.mark.parametrize("cell", benchtiny.cells())
def test_schedule_is_a_function_of_the_seed(cell, tmp_path):
    """One seed gives the same schedule; another gives the same amount of
    work in another order."""
    root = benchtiny.make_root(tmp_path)
    _, cfg, mix = H.find_cell(H.benchmark(root), cell, root)
    gen = H.generator(mix, root)

    def make(seed):
        src, dst = R.dag_like(cfg["n"], cfg["m"], seed=seed,
                              back_frac=cfg["generator"]["back_frac"])
        log = R.EdgeLog(cfg["n"], src, dst)
        return gen.make(mix, log, H.rng_for(seed, 1), 5.0)

    a, b, c = make(SEED), make(SEED), make(SEED + 1)
    np.testing.assert_array_equal(a.u, b.u)
    np.testing.assert_array_equal(a.v, b.v)
    assert [o.kind for o in a.ops] == [o.kind for o in b.ops]
    assert a.u.size == c.u.size and not np.array_equal(a.u, c.u)
    assert sorted(o.kind for o in a.ops) == sorted(o.kind for o in c.ops)


def test_fixed_seeds_give_every_seed_the_same_work(tmp_path):
    """A configuration and a mix with a ``fixed_seed``: every run seed gets
    one graph and one schedule; the seed shuffles the edge list."""
    root = benchtiny.make_root(tmp_path)
    _, cfg, mix = H.find_cell(H.benchmark(root), "wiki.bulk", root)
    assert "fixed_seed" in cfg["generator"] and "fixed_seed" in mix
    gen = H.generator(mix, root)
    (sa, da, a, la), (sb, db, b, lb) = (
        H.make_data(cfg, mix, gen, seed, 5.0) for seed in (SEED, SEED + 1))
    for x, y in ((a.u, b.u), (a.v, b.v), (a.warm_u, b.warm_u),
                 (la.src, lb.src), (la.dst, lb.dst)):
        np.testing.assert_array_equal(x, y)
    key = (lambda s, d: np.sort(s.astype(np.int64) * cfg["n"] + d))
    np.testing.assert_array_equal(key(sa, da), key(sb, db))
    assert not np.array_equal(sa, sb)

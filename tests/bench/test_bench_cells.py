"""Each cell's traffic end to end at a tiny size on the CPU, with the
kernels in the Pallas interpreter: the result line has its keys, the
cell's metrics and ``correct``; the command refuses a host with no TPU;
a configuration, a graph generator, a mix and a metric dropped in as new
files are found by name; the schedule is a function of the seed alone."""
import hashlib
import json

import numpy as np
import pytest

import benchtiny
from bench import harness as H
from bench import reference as R

SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
#: sha256 of ``wiki.bulk``'s graph (the shuffled edge list, then the edge
#: log) and of its schedule at ``benchtiny``'s sizes, per seed, recorded
#: when the harness called ``dag_like`` itself: the graph generator found
#: by name has to make the same bytes.  At full size both hashes were
#: compared once, outside the suite.
WIKI_TINY = {
    SEED: ("d251e20449d2c5a03fbc9f5951910ecbb804e9c8b20fd9760b408d75474ee5b3",
           "029ca08e5b03c9413d0a5bab04c9046ade46c8dd83183ac1a74b6a109e892c27"),
    5: ("b153bf854b6178ec24e1e7016f95af047d0348400d77307310c8333ee9c09e52",
        "029ca08e5b03c9413d0a5bab04c9046ade46c8dd83183ac1a74b6a109e892c27"),
}
#: a graph generator dropped in as a new file: a path through every
#: vertex and random forward chords of it
PATH_CHORDS = """import numpy as np


def make(cfg, seed):
    n, m = cfg["n"], cfg["m"]
    rng = np.random.default_rng(seed)
    a = rng.integers(0, n - 1, m - n + 1)
    src = np.concatenate([np.arange(n - 1), a])
    dst = np.concatenate([np.arange(1, n), a + 1 + rng.integers(0, n - 1 - a)])
    return src.astype(np.int32), dst.astype(np.int32)
"""


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
    """A new configuration, graph generator, mix and metric, and the
    entries naming them, run without an edit to any file the benchmark
    already has."""
    root = benchtiny.make_root(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "email-euall.json").read_text())
    cfg = dict(cfg, name="tiny-new", n=2000, m=3000,
               generator={"name": "path_chords", "fixed_seed": 9})
    (b / "configs" / "tiny-new.json").write_text(json.dumps(cfg))
    (b / "graphs" / "path_chords.py").write_text(PATH_CHORDS)
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
    _, cfg, mix = H.find_cell(bm, "tiny.new", root)
    src, dst, *_ = H.make_data(cfg, mix, H.generator(mix, root), 3, 1.0,
                               root)
    edges = set(zip(src.tolist(), dst.tolist()))
    assert all((i, i + 1) in edges for i in range(cfg["n"] - 1))


def test_a_missing_graph_generator_fails_at_once(tmp_path, monkeypatch):
    """A configuration that names a generator with no file fails, naming
    the file, before anything is set up."""
    root = benchtiny.make_root(tmp_path)
    benchtiny.edit_json(root / "bench" / "configs" / "email-euall.json",
                        generator={"name": "no_such_graph"})

    def set_up(*a, **kw):
        raise AssertionError("set-up began")
    monkeypatch.setattr(H, "set_up", set_up)
    with pytest.raises(FileNotFoundError, match=r"graphs/no_such_graph\.py"):
        H.run_cell("email.churn", 3, 1.0, trace=False,
                   backend="pallas-interpret", root=root)


@pytest.mark.parametrize("seed", sorted(WIKI_TINY))
def test_wiki_bulk_data_is_unchanged(seed, tmp_path):
    root = benchtiny.make_root(tmp_path)
    _, cfg, mix = H.find_cell(H.benchmark(root), "wiki.bulk", root)
    src, dst, s, log = H.make_data(cfg, mix, H.generator(mix, root), seed,
                                   51.0, root)
    graph, sched = hashlib.sha256(), hashlib.sha256()
    for a in (src, dst, log.src, log.dst):
        graph.update(a.tobytes())
    for a in (s.u, s.v, s.warm_u, s.warm_v, s.version):
        sched.update(a.tobytes())
    assert (graph.hexdigest(), sched.hexdigest()) == WIKI_TINY[seed]
    assert not s.ops and not s.warm_ops


def test_closed_cycle_places_updates_between_batches(tmp_path):
    """A closed mix with a ``cycle`` repeats it until ``max_batches``
    batches: each update after the batches before it, each batch at the
    version the updates before it made, and room in the edge capacity for
    every insert of the schedule and the set-up."""
    root = benchtiny.make_root(tmp_path)
    _, cfg, mix = H.find_cell(H.benchmark(root), "email.churn", root)
    mix = dict(mix, max_batches=7)      # one cycle of 5 batches, then 2
    gen = H.generator(mix, root)
    _, _, s, log = H.make_data(cfg, mix, gen, SEED, 5.0, root)
    b = mix["batch"]
    assert mix["cycle"] == ["batch", "insert", "batch", "insert", "batch",
                            "insert", "batch", "delete", "batch", "rebuild"]
    assert s.loop == "closed" and s.u.size == 7 * b
    assert [o.kind for o in s.warm_ops] == [
        "insert", "insert", "insert", "delete", "rebuild"] * mix["warm_cycles"]
    assert [(o.kind, o.after) for o in s.ops] == [
        ("insert", 1), ("insert", 2), ("insert", 3), ("delete", 4),
        ("rebuild", 5), ("insert", 6)]
    version = sum(o.kind != "rebuild" for o in s.warm_ops)
    ops = iter(s.ops)
    op = next(ops, None)
    for j in range(7):
        while op is not None and op.after == j:
            version += op.kind != "rebuild"
            assert op.version == version
            op = next(ops, None)
        assert set(s.version[j * b:(j + 1) * b]) == {version}
    inserted = (3 * mix["warm_cycles"] + 4) * mix["insert_batch"]
    assert gen.max_inserted_edges(mix, 51.0) == inserted
    assert log.src.size == cfg["m"] + inserted


@pytest.mark.parametrize("cell", benchtiny.cells())
def test_schedule_is_a_function_of_the_seed(cell, tmp_path):
    """One seed gives the same schedule; another gives the same amount of
    work in another order."""
    root = benchtiny.make_root(tmp_path)
    _, cfg, mix = H.find_cell(H.benchmark(root), cell, root)
    gen = H.generator(mix, root)

    def make(seed):
        src, dst = H.graph_generator(cfg, root).make(cfg, seed)
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

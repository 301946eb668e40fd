"""The reduction of the program's own spans (``bench/program_spans.py``),
the report's time by named scope (``bench/program_report.py``) and the
readers of the metrics that read the engine's BFS level and fixpoint
round counters, on a trace recorded on a TPU v5e
(``bulk_tiny``: a 1.5-s ``wiki.bulk`` run at ``benchtiny``'s sizes of a
program with the spans, kept with only the ``python`` line of the host's
threads), on the older ``churn_tiny`` trace of a program without them,
and on records made here."""
import json
import pathlib

import numpy as np
import pytest

from bench import harness as H
from bench import program_report as PR
from bench import program_spans as PS
from bench import trace_reduce

TESTDATA = pathlib.Path(H.ROOT) / "bench" / "testdata"
BULK = TESTDATA / "bulk_tiny.xplane.pb.gz"
CHURN = TESTDATA / "churn_tiny.xplane.pb.gz"
COUNTERS = ("bfs_levels_per_dispatch", "bfs_open_lane_pct",
            "bfs_proof_level_pct", "insert_rounds_per_batch",
            "rebuild_rounds_per_rebuild")
TRACED = ("bfs_ms_per_level", "insert_ms_per_round",
          "frontend_host_us_per_kq")


@pytest.fixture(scope="module")
def bulk():
    pd = trace_reduce.load(BULK)
    return trace_reduce.reduce_profile(pd), PS.reduce_program(pd)


def test_program_spans_of_a_chip_trace(bulk):
    reduced, prog = bulk
    spans = prog["spans"]
    for name in ("server.submit", "server.flush", "engine.label_dispatch",
                 "engine.wait_nu", "engine.wait_hits", "engine.bfs_dispatch",
                 "engine.scatter"):
        assert spans[name]["count"] > 0, name
    assert spans["server.submit"]["count"] == spans["server.flush"]["count"]
    for s in spans.values():
        assert 0 <= s["self_s"] <= s["total_s"] + 1e-9
    assert 0 < prog["frontend_host_s"] < reduced["window_s"]
    # the waits are most of a flush: its host time is less than its span
    assert prog["frontend_host_s"] < spans["server.flush"]["total_s"] \
        + spans["server.submit"]["total_s"]
    idle = prog["idle_program_s"]
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert set(idle) <= set(spans) | {"-"}


def test_a_trace_without_program_spans_reduces_to_nothing():
    prog = PS.reduce_program(trace_reduce.load(CHURN))
    assert prog == {"spans": {}, "frontend_host_s": None,
                    "idle_program_s": None}


def test_self_time_subtracts_nested_spans():
    spans = [("a", 0, 100), ("b", 10, 40), ("c", 20, 30), ("b", 50, 60)]
    got = PS._self_times(spans)
    assert got == pytest.approx({"a": 60e-9, "b": 30e-9, "c": 10e-9})


def _record(bulk, **engine):
    reduced, prog = bulk
    return dict(queries=20000, ops=[dict(kind="insert"), dict(kind="insert"),
                                    dict(kind="rebuild")],
                engine=engine, trace=dict(reduced, program=prog,
                                          programs=dict(reduced["programs"],
                                                        insert_impl=0.5)))


ENGINE = dict(bfs_dispatches=4, bfs_levels=40, bfs_slot_levels=40 * 256,
              bfs_open_lane_levels=4096, bfs_proof_levels=12,
              insert_rounds=50, insert_rounds_batches=2, rebuild_rounds=30,
              rebuilds=1)


@pytest.mark.parametrize("name", COUNTERS + TRACED)
def test_reader_reads_its_counters(bulk, name):
    value = H.read_metric(name, _record(bulk, **ENGINE))
    assert isinstance(value, float) and np.isfinite(value) and value > 0
    # a program without the counters or spans (the parent of this one):
    # nothing to read, and no error
    reduced, _ = bulk
    old = dict(_record(bulk), trace=reduced)
    assert H.read_metric(name, old) is None
    if name in TRACED:
        assert H.read_metric(name, _record(bulk, **ENGINE) | {"trace": None}) \
            is None


def test_readers_compute_what_they_say(bulk):
    rec = _record(bulk, **ENGINE)
    reduced, prog = bulk
    assert H.read_metric("bfs_levels_per_dispatch", rec) == 10.0
    assert H.read_metric("bfs_open_lane_pct", rec) == pytest.approx(40.0)
    assert H.read_metric("bfs_proof_level_pct", rec) == pytest.approx(30.0)
    assert H.read_metric("insert_rounds_per_batch", rec) == 25.0
    assert H.read_metric("insert_ms_per_round", rec) == pytest.approx(10.0)
    assert H.read_metric("rebuild_rounds_per_rebuild", rec) == 30.0
    assert H.read_metric("bfs_ms_per_level", rec) == pytest.approx(
        reduced["programs"]["coalesced"] * 1e3 / 40)
    assert H.read_metric("frontend_host_us_per_kq", rec) == pytest.approx(
        prog["frontend_host_s"] * 1e6 / 20)


def test_the_recorded_trace_is_small():
    assert BULK.stat().st_size <= 2 * 2 ** 20
    json.dumps(PS.reduce_program(trace_reduce.load(BULK)))


def test_insert_rounds_count_only_the_synced_inserts(bulk):
    """Rounds are synced at flush boundaries: inserts after the window's
    last flush count in neither ``insert_rounds`` nor its batches, while
    the window's device time covers every insert."""
    rec = _record(bulk, **dict(ENGINE, insert_rounds=25,
                               insert_rounds_batches=1))
    assert H.read_metric("insert_rounds_per_batch", rec) == 25.0
    assert H.read_metric("insert_ms_per_round", rec) == pytest.approx(10.0)


def test_report_readers_exist():
    for name in PR.READERS:
        assert (TESTDATA.parent / "metrics" / f"{name}.py").is_file(), name


def test_time_by_scope_splits_the_program(bulk):
    """Every non-container op event of ``coalesced`` counts once, under
    the scope the HLO map gives its instruction (``-`` where none)."""
    pd = trace_reduce.load(BULK)
    reduced, _ = bulk
    plain = PR.time_by_scope(pd, {}, top=10 ** 6)
    assert set(plain["s"]) == {"-"}
    total = plain["s"]["-"]
    assert 0 < total <= reduced["programs"]["coalesced"]
    assert total == pytest.approx(sum(sec for *_, sec in plain["ops"]))
    first = plain["ops"][0][0]
    split = PR.time_by_scope(pd, {first: "bfs.reduce"})
    assert split["s"]["bfs.reduce"] == pytest.approx(plain["ops"][0][3])
    assert sum(split["s"].values()) == pytest.approx(total)
    assert split["ops"][0][1] == "bfs.reduce"


def test_hlo_scopes_name_the_level_body():
    """The compiled ``coalesced`` program's instructions carry the named
    scopes of the BFS level body and the re-check (CPU, tiny engine)."""
    from repro.core import DBLIndex, make_graph
    from repro.serve.engine import QueryEngine
    rng = np.random.default_rng(0)
    src = rng.integers(0, 63, 160).astype(np.int32)
    dst = np.minimum(src + rng.integers(1, 6, 160), 63).astype(np.int32)
    idx = DBLIndex.build(make_graph(src, dst, 64, m_cap=256), n_cap=64, k=2,
                         k_prime=2, max_iters=16)
    eng = QueryEngine(idx, bfs_chunk=32, max_iters=16)
    scopes = set(PR.hlo_scopes(PR.coalesced_hlo(eng)).values())
    assert {"bfs.gather", "bfs.reduce", "bfs.update", "bfs.recheck",
            "while.cond"} <= scopes


def test_report_reduces_a_saved_trace(capsys, tmp_path):
    out = tmp_path / "report.json"
    assert PR.main(["--load", str(BULK), "--out", str(out)]) == 0
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed == json.loads(out.read_text())
    assert printed["program"]["frontend_host_s"] > 0
    assert "scopes" not in printed

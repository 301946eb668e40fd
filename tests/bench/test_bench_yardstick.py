"""The benchmark's arithmetic: bytes from shapes, the peak table, the
trace reduction (on a trace recorded on a TPU v5e) and every metric
reader named in BENCHMARK.json."""
import json
import pathlib

import numpy as np
import pytest

import benchtiny
from bench import harness as H
from bench import peaks, roofline, trace_reduce

TESTDATA = pathlib.Path(H.ROOT) / "bench" / "testdata"
TRACE = TESTDATA / "churn_tiny.xplane.pb.gz"


def test_dbl_query_bytes_from_shapes():
    # eight 2-word label rows, three flags and a verdict: 80 B a lane
    assert roofline.dbl_query_bytes(4096, k=64, k_prime=64) == 4096 * 80
    assert roofline.dbl_query_bytes(512, k=32, k_prime=96, nflags=1) \
        == 512 * ((4 * 1 + 4 * 3) * 4 + 4 + 4)
    assert roofline.dbl_query_bytes(16, k=64, k_prime=64, il_dim=4) \
        == 16 * (80 + 4 * 2 * 4 * 4)


def test_roofline_share():
    assert roofline.roofline_share(819e9, 2.0, 819e9) == pytest.approx(50.0)
    assert roofline.roofline_share(1.0, 0.0, 819e9) is None


def test_peaks_table_holds_the_v5e_and_refuses_others():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_trace_reduction_of_a_chip_trace(reduced):
    r = reduced
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(r["idle_s"].values())
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)
    for prog in ("label_phase", "insert_impl"):
        assert r["programs"][prog] > 0
    assert r["kernel"]["calls"] > 0
    assert all(q and q % 512 == 0 or q in (16, 32, 64, 128, 256)
               for q in r["kernel"]["lanes"])
    assert set(r["kernel"]["flags"]) == {3}
    assert set(r["idle_s"]) <= set(trace_reduce.SPANS) | {"loop"}
    assert r["spans"]["insert"] > 0 and r["spans"]["rebuild"] > 0
    assert r["rebuild_device_s"] > 0
    bd = r["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10


def test_trace_reduction_names_programs_and_ops():
    assert trace_reduce.program_name("jit_label_phase(1134)") == "label_phase"
    assert trace_reduce.op_kind("%fusion.13 = s32[4]{0} fusion(...)") \
        == "fusion"
    assert trace_reduce.op_kind("%pad.6.clone = s32[3] pad(...)") == "pad"
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]


def _record(reduced):
    cfg = json.loads((H.ROOT / "bench/configs/email-euall.json").read_text())
    return dict(setup_s=12.5, window_s=10.0, queries=20000,
                latency_s=np.linspace(0.0, 1.0, 101),
                ops=[dict(kind="insert", due=1.0, start=1.1, end=1.6),
                     dict(kind="insert", due=2.0, start=2.0, end=2.4),
                     dict(kind="delete", due=3.0, start=3.0, end=3.01),
                     dict(kind="rebuild", due=4.0, start=4.0, end=5.5)],
                late_s=np.linspace(0.0, 0.2, 101),
                engine={"queries": 20000,
                        "prune_hits": {"dl": 1, "bl": 1, "il": 0, "thm": 0,
                                       "bfs": 5000}},
                trace=reduced, config=cfg,
                device_kind="TPU v5 lite")


METRICS = {m["name"]: m for m in benchtiny.benchmark()["end_to_end"]
           + benchtiny.benchmark()["per_layer"]}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_every_metric_has_a_reader(name, reduced):
    """Each metric BENCHMARK.json names reads a finite number from a full
    record, and a device-trace metric reads nothing without a trace."""
    rec = _record(reduced)
    value = H.read_metric(name, rec)
    assert isinstance(value, float) and np.isfinite(value), value
    if METRICS[name]["unit"] == "%":
        assert 0 <= value <= 100
    if METRICS[name]["source"] == "device_trace":
        assert H.read_metric(name, dict(rec, trace=None)) is None


def test_metric_readers_compute_what_they_say(reduced):
    rec = _record(reduced)
    assert H.read_metric("queries_per_s", rec) == 2000.0
    assert H.read_metric("query_p95_ms", rec) == pytest.approx(950.0)
    assert H.read_metric("insert_p50_ms", rec) == pytest.approx(500.0)
    assert H.read_metric("rebuild_p50_ms", rec) == pytest.approx(1500.0)
    assert H.read_metric("bfs_lane_pct", rec) == pytest.approx(25.0)
    assert H.read_metric("gen_late_p95_ms", rec) == pytest.approx(190.0)
    assert H.read_metric("device_idle_pct.churn", rec) \
        == H.read_metric("device_idle_pct", rec)

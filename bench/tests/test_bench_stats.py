"""Metric arithmetic: percentiles, rates and the trace reduction on
hand-made request logs and planes (CPU)."""
import math
from types import SimpleNamespace as NS

import pytest

import bench_testkit  # noqa: F401 - puts bench/ on the path
from yardstick import stats, tracereduce
from yardstick.roofline import hbm_share


def test_latencies_count_failures_as_missing():
    log = [(0.0, 0.010, True), (1.0, 1.020, True), (2.0, None, False),
           (3.0, 3.5, False)]
    lat = stats.latencies(log)
    assert lat[:2] == pytest.approx([0.010, 0.020])
    assert lat[2] == lat[3] == stats.MISSING


def test_percentile_is_nearest_rank_over_every_request():
    values = [float(i) for i in range(1, 101)]
    assert stats.percentile(values, 0.50) == 50.0
    assert stats.percentile(values, 0.99) == 99.0
    assert stats.percentile(values, 1.0) == 100.0
    # one failure in a hundred pushes the 99th percentile to the 99th
    # answered value; two push it past every answer
    assert stats.percentile(values[:99] + [math.inf], 0.99) == 99.0
    assert stats.percentile(values[:98] + [math.inf] * 2, 0.99) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_rate_over_the_whole_window():
    assert stats.rate(300, 10.0) == 30.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def _ev(name, s, e):
    return NS(name=name, start_ns=s * 1e9, duration_ns=(e - s) * 1e9)


def _planes():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion.1", 1.0, 2.0), _ev("fusion.2", 1.5, 2.5),
                                   _ev("copy", 4.0, 5.0), _ev("early", 0.0, 0.5)]),
        NS(name="XLA Modules", events=[_ev("jit_run(3)", 1.0, 2.5),
                                       _ev("jit_tiled_block(7)", 4.0, 5.0)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench:window", 0.8, 6.0), _ev("bench:submit", 2.6, 3.0),
        _ev("bench:wait", 3.0, 6.0), _ev("unrelated", 0, 9)])])
    return [dev, host, NS(name="/host:metadata", lines=[])]


def test_trace_reduction_on_hand_made_planes():
    got = tracereduce.reduce_trace(tracereduce.read_planes(_planes()))
    assert got["window_s"] == pytest.approx(5.2)
    # ops inside the window: [1, 2.5] and [4, 5]
    assert got["busy_s"] == pytest.approx(2.5)
    assert got["program_s"] == pytest.approx({"jit_run": 1.5, "jit_tiled_block": 1.0})
    assert [n for n, _ in got["device_ops"]] == [
        "jit_run/fusion.1", "jit_run/fusion.2", "jit_tiled_block/copy"]
    # gaps: [0.8, 1] other, [2.5, 4] mid 3.25 in wait, [5, 6] in wait
    assert got["idle_gaps"][0] == ["bench:wait", pytest.approx(1.5)]
    assert got["idle_by_label"] == pytest.approx({"bench:wait": 2.5, "bench:other": 0.2})


def test_trace_reduction_needs_window_and_device():
    planes = _planes()
    assert tracereduce.reduce_trace(tracereduce.read_planes(planes[1:])) is None
    planes[1].lines[0].events = planes[1].lines[0].events[1:]
    assert tracereduce.reduce_trace(tracereduce.read_planes(planes)) is None


def test_union_and_program_names():
    assert tracereduce.union_length([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert tracereduce.program_name("jit_threshold_pallas(12)") == "jit_threshold_pallas"
    assert tracereduce.op_name("%pad.0 = u32[64,8]{1,0} pad(u32[64,4] %bm)") == "pad.0"


def test_hbm_share_counts_named_programs_and_says_nothing_without_them():
    rec = {"trace": {"program_s": {"jit_run": 0.5, "other": 9.0}},
           "peaks": {"hbm_bytes_per_s": 1e9}, "needed_bytes": {"fused": 2e8}}
    assert hbm_share(rec, "fused", ("jit_run",)) == pytest.approx(40.0)
    assert hbm_share(rec, "tiled_fused", ("jit_run",)) is None
    assert hbm_share(rec, "fused", ("jit_tiled_block",)) is None
    assert hbm_share(dict(rec, trace=None), "fused", ("jit_run",)) is None


def test_trace_reduction_on_a_trace_recorded_on_the_chip():
    """A short traced run of an 8-query panel served through QueryServer
    (the multi-query fused runner ``jit_run``) on a TPU v5 lite, cut to the
    planes and lines the reduction reads (``tests/data``)."""
    import gzip
    import json
    from pathlib import Path

    path = Path(__file__).parent / "data" / "uniform_panel_trace.json.gz"
    with gzip.open(path, "rt") as f:
        planes = [NS(name=p["name"], lines=[
            NS(name=ln["name"], events=[NS(name=n, start_ns=s, duration_ns=d)
                                        for n, s, d in ln["events"]])
            for ln in p["lines"]]) for p in json.load(f)["planes"]]
    raw = tracereduce.read_planes(planes)
    assert list(raw["devices"]) == ["/device:TPU:0"]
    got = tracereduce.reduce_trace(raw)
    assert 0 < got["busy_s"] < got["window_s"]
    assert "jit_run" in got["program_s"]
    assert sum(got["program_s"].values()) <= got["window_s"] * 1.0001
    assert got["device_ops"][0][0].startswith("jit_run/")
    labels = {label for label, _secs in got["idle_gaps"]}
    assert labels <= {"bench:submit", "bench:wait", "bench:other"}
    idle = got["window_s"] - got["busy_s"]
    assert sum(got["idle_by_label"].values()) == pytest.approx(idle, rel=1e-6)

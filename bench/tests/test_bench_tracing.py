"""The trace reduction on a small synthetic trace."""
import pytest

import _paths  # noqa: F401
import tracing


def test_busy_union_idle_and_gaps():
    # two devices; ops overlap on d0; a host span tree a > b, a > c
    devices = {"d0": [("fusion", 0, 10), ("fusion", 5, 30),
                      ("while", 60, 70)],
               "d1": [("fusion", 0, 50)]}
    spans = [("bench.a", 0, 100), ("bench.b", 10, 40),
             ("bench.c", 50, 80)]
    red = tracing.reduce(devices, spans, 0, 100)
    assert red["n_devices"] == 2
    # d0 busy 30 + 10, d1 busy 50: mean 45 ns of 100
    assert red["busy_s"] == pytest.approx(45e-9)
    assert red["busy_s_total"] == pytest.approx(90e-9)
    assert red["idle_pct"] == pytest.approx(55.0)
    gaps = dict(red["breakdown"]["idle_gaps"])
    # d0 gaps: (30, 60) mid 45 -> a; (70, 100) mid 85 -> a
    # d1 gap: (50, 100) mid 75 -> c
    assert gaps == {"bench.a": pytest.approx(60e-9),
                    "bench.c": pytest.approx(50e-9)}
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion"] == pytest.approx((10 + 25 + 50) * 1e-9)
    assert red["longest_gap_s"] == pytest.approx(50e-9)


def test_window_clips_and_no_span():
    devices = {"d0": [("x", -10, 5), ("x", 95, 120)]}
    red = tracing.reduce(devices, [], 0, 100)
    assert red["busy_s"] == pytest.approx(10e-9)
    assert dict(red["breakdown"]["idle_gaps"]) == \
        {"host:none": pytest.approx(90e-9)}


def test_innermost_span():
    tl = tracing.span_timeline([("bench.a", 0, 100), ("bench.b", 10, 20)])
    assert [tracing.span_at(tl, t) for t in (-1, 5, 15, 20, 99, 100)] == \
        [None, "bench.a", "bench.b", "bench.a", "bench.a", None]


READERS = ["device_idle.study", "device_idle.service", "event_ns.study",
           "event_ns.service", "event_roofline.study"]


@pytest.mark.parametrize("metric", READERS)
def test_readers_return_nothing_without_a_trace(metric):
    import run_cell
    read = run_cell.load_reader(metric)
    assert read({"trace": None, "lane_events": None}) is None
    assert read({"trace": {"busy_s_total": 0.0, "idle_pct": 100.0},
                 "lane_events": 10}) is None


def test_roofline_share_from_the_work_model():
    import json
    import os
    import run_cell
    import workmodel
    cfg = json.load(open(os.path.join(_paths.BENCH, "configs",
                                      "paper-homog.json")))
    # 10**9 float32 lane-events of 88 B each at 819 GB/s take 0.1074 s
    run = {"trace": {"busy_s_total": 0.2149}, "lane_events": 10 ** 9,
           "cfg": cfg, "device_kind": "TPU v5 lite", "workmodel": workmodel}
    share = run_cell.load_reader("event_roofline.study")(run)
    assert share == pytest.approx(88e9 / 819e9 / 0.2149 * 100.0)
    assert run_cell.load_reader("event_ns.study")(run) == pytest.approx(
        0.2149)

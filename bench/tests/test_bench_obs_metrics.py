"""The readers of the program's own spans and counters, on a synthetic
recorder state: the window's units are the last ones, the warm-up's are
skipped, and a program without the recorder reads as no metric."""
import sys

import pytest

import _paths  # noqa: F401
import run_cell
from repro import obs

R = obs.Record
MS = 1_000_000


def dispatch(id, parent, t0, t1, events, steps):
    return R(id, parent, "repro.sweep.dispatch", t0, t1, {},
             {"lane_events": events, "lane_steps_run": steps})


def device(id, parent, t0, t1, chip=0):
    return R(id, parent, "repro.sweep.device", t0, t1, {"device": chip}, {})


def study(id, t0, t1):
    return R(id, None, "repro.study", t0, t1, {}, {})


class Loop:
    def __init__(self, done):
        self.done = done


# a warm-up study (its numbers far off), then two window studies; the
# second runs on two chips whose device intervals overlap
STUDIES = [
    study(1, 0, 1000 * MS), dispatch(2, 1, 0, 1 * MS, 1, 1000),
    device(3, 2, 0, 990 * MS),
    study(10, 2000 * MS, 2100 * MS),
    R(11, 10, "repro.study.prepare", 2000 * MS, 2010 * MS, {}, {}),
    dispatch(12, 10, 2010 * MS, 2011 * MS, 300, 400),
    dispatch(13, 10, 2011 * MS, 2012 * MS, 100, 400),
    device(14, 12, 2011 * MS, 2050 * MS), device(15, 13, 2050 * MS,
                                               2090 * MS),
    study(20, 3000 * MS, 3100 * MS),
    dispatch(21, 20, 3000 * MS, 3001 * MS, 600, 800),
    device(22, 21, 3001 * MS, 3060 * MS, chip=0),
    device(23, 21, 3001 * MS, 3081 * MS, chip=1),
]


@pytest.fixture
def recorded(monkeypatch):
    def use(recs):
        monkeypatch.setattr(obs, "records", lambda timeout=60.0: list(recs))
    return use


def read(metric, loop):
    return run_cell.load_reader(metric)({"loop": loop})


def test_study_readers_skip_the_warm_up(recorded):
    recorded(STUDIES)
    loop = Loop([object(), object()])
    # (300 + 100 + 600) events over (400 + 400 + 800) steps
    assert read("lane_fill.study", loop) == pytest.approx(62.5)
    # device: 39 + 40 ms, then 59 + 80 ms over two chips; 1000 events
    assert read("dispatch_event_ns.study", loop) == pytest.approx(
        (39 + 40 + 59 + 80) * MS / 1000)
    # uncovered: 100 - 79 = 21 ms, and 100 - 80 (union of chips) = 20 ms
    assert read("grid_host_ms.study", loop) == pytest.approx(20.5)
    # one window study only: the last
    one = Loop([object()])
    assert read("lane_fill.study", one) == pytest.approx(75.0)
    assert read("grid_host_ms.study", one) == pytest.approx(20.0)


def test_service_readers_skip_the_warm_up(recorded):
    recs = []
    # tick t: span [100 t, 100 t + 90] ms, oracle [20, 80] ms into it,
    # device [30, 75] ms; a dispatch counts 100 + t events of 200 steps
    for t in range(5):
        base, i = 100 * t * MS, 10 * t + 1
        recs += [R(i, None, "repro.service.tick", base, base + 90 * MS,
                   {"tick": t}, {}),
                 R(i + 1, i, "repro.service.oracle", base + 20 * MS,
                   base + (80 - t) * MS, {}, {}),
                 dispatch(i + 2, i + 1, base + 21 * MS, base + 22 * MS,
                          100 + t, 200),
                 device(i + 3, i + 2, base + 30 * MS, base + 75 * MS)]
    recorded(recs)
    # two scenario runs of 2 and 1 ticks: the last 3 ticks (2, 3, 4)
    loop = Loop([{"n_ticks": 2}, {"n_ticks": 1}])
    assert read("lane_fill.service", loop) == pytest.approx(
        (102 + 103 + 104) / 600 * 100)
    # oracle host: 60 - t - 45 ms for t = 2, 3, 4 -> median 12 ms
    assert read("oracle_host_ms.service", loop) == pytest.approx(12.0)


@pytest.mark.parametrize("metric", [
    "lane_fill.study", "dispatch_event_ns.study", "grid_host_ms.study",
    "lane_fill.service", "oracle_host_ms.service"])
def test_no_recorder_or_too_few_units_read_as_nothing(metric, recorded,
                                                      monkeypatch):
    loop = Loop([{"n_ticks": 1}] * 9)
    recorded(STUDIES)
    assert read(metric, loop) is None            # fewer units than asked
    recorded([])
    assert read(metric, Loop([{"n_ticks": 1}])) is None
    # a program without the recorder
    monkeypatch.delattr(sys.modules["repro"], "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(metric, Loop([{"n_ticks": 1}])) is None

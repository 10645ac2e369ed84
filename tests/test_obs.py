"""The in-process recorder (`repro.obs`) and the spans and counters the
sweep and the service record with it."""
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core, obs
from repro.core.des import (SCAN_SEG, pack_workload, resolve_ring,
                            simulate_packet_scan_lanes)
from repro.core.sweep import run_window_oracle
from repro.service import ServiceConfig, run_service
from repro.workload.lublin import WorkloadParams, generate_workload


def test_nesting_parent_ids_and_counts():
    rec = obs.Recorder()
    with rec.span("repro.t.a", x=1) as a:
        with rec.span("repro.t.b") as b:
            pass
        with rec.span("repro.t.c") as c:
            rec.count("n", 2)
            rec.count("n", 3)
        rec.count("m", 1)

        def worker():
            with rec.span("repro.t.thread") as sp:
                other.append(sp)
        other = []
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    b.count("late", 7)                  # a count after the span closed
    got = {r.name: r for r in rec.records()}
    assert [r.name for r in rec.records()] == [
        "repro.t.b", "repro.t.c", "repro.t.thread", "repro.t.a"]
    assert got["repro.t.a"].parent is None and got["repro.t.a"].attrs == \
        {"x": 1}
    assert got["repro.t.b"].parent == a.id == got["repro.t.c"].parent
    assert got["repro.t.c"].counts == {"n": 5}
    assert got["repro.t.a"].counts == {"m": 1}
    assert got["repro.t.b"].counts == {"late": 7}
    assert a.t0 <= b.t0 <= b.t1 <= c.t0 <= c.t1 <= a.t1
    # another thread's spans do not nest under this thread's
    assert other[0].parent is None
    rec.count("nothing open", 1)        # no span open: nothing to count on


def test_self_time_is_span_minus_children():
    R = obs.Record
    parent = R(1, None, "p", 0, 100, {}, {})
    recs = [parent, R(2, 1, "c", 10, 30, {}, {}),
            R(3, 1, "c", 20, 50, {}, {}), R(4, 1, "c", 60, 70, {}, {}),
            R(5, 2, "grandchild", 0, 100, {}, {}),
            R(6, None, "other", 0, 100, {}, {})]
    # children cover [10, 50] and [60, 70]
    assert obs.self_ns(parent, recs) == 100 - 40 - 10


def test_buffer_stays_bounded():
    rec = obs.Recorder(max_records=10)
    for i in range(25):
        with rec.span("repro.t.s", i=i):
            pass
    got = rec.records()
    assert len(got) == 10
    assert [r.attrs["i"] for r in got] == list(range(15, 25))
    rec.clear()
    assert rec.records() == []


def test_stamps_in_order_and_never_before_enqueue():
    rec = obs.Recorder()
    f = jax.jit(lambda x: jnp.sin(x) @ jnp.cos(x).T)
    x = jnp.ones((256, 256))
    jax.block_until_ready(f(x))
    enqueued, spans = [], []
    for i in range(6):
        with rec.span("repro.t.dispatch", i=i) as sp:
            out = f(x + i)
            enqueued.append(time.time_ns())
            rec.stamp_when_ready("repro.t.device", out)
        spans.append(sp)
    jax.block_until_ready(out)
    stamps = [r for r in rec.records() if r.name == "repro.t.device"]
    assert len(stamps) == 6
    assert [s.parent for s in stamps] == [sp.id for sp in spans]
    for s, e in zip(stamps, enqueued):
        assert s.t0 >= e and s.t1 >= s.t0
        assert s.attrs["device"] == out.devices().pop().id
    for a, b in zip(stamps, stamps[1:]):
        assert b.t0 >= a.t1             # one device: back to back, in order


def _absolute_starts(logdir, name):
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    start = dict(next(p for p in data.planes
                      if p.name == "Task Environment").stats)
    return [int(start["profile_start_time"]) + int(ev.start_ns)
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events if ev.name == name]


def test_span_lands_in_the_profiler_trace_on_the_same_clock(tmp_path):
    rec = obs.Recorder()
    with jax.profiler.trace(str(tmp_path)):
        with rec.span("repro.t.traced") as sp:
            time.sleep(0.005)
    starts = _absolute_starts(str(tmp_path), "repro.t.traced")
    assert len(starts) == 1
    assert abs(starts[0] - sp.t0) < 1_000_000


def _dispatches(recs):
    return [r for r in recs if r.name == "repro.sweep.dispatch"]


def _events(n_jobs, n_groups):
    n_groups = np.asarray(n_groups)
    return n_jobs * n_groups.size + 2 * int(n_groups.sum())


def test_chunked_study_and_oracle_record_their_dispatches():
    n = 150
    wls = {f"w{i}": generate_workload(WorkloadParams(
        n_jobs=n, nodes=64, load=0.9, homogeneous=True, seed=i))
        for i in range(2)}
    cohort = core.group_workloads(wls, {w: np.float32 for w in wls})[0]
    ks, s_props = (0.5, 1, 2, 5, 10, 50, 100, 500), (0.05, 0.1, 0.3, 0.5,
                                                     0.7)
    obs.clear()
    # 40 lanes, chunks of at most 16: 3 chunks of width 14, the last
    # with 12 real lanes and 2 sentinels
    grids = core.run_cohort_grid(cohort, ks=ks, s_props=s_props,
                                 mode="chunked", chunk_lanes=16)
    recs = obs.records()
    study = [r for r in recs if r.name == "repro.study"]
    assert len(study) == 1
    assert study[0].attrs == {"workloads": 2, "ks": 8, "s_props": 5,
                              "layout": "chunked", "chips": 1}
    kids = {r.name for r in recs if r.parent == study[0].id}
    assert kids == {"repro.study.prepare", "repro.sweep.dispatch",
                    "repro.study.gather"}
    ds = _dispatches(recs)
    assert len(ds) == 2 * 3
    assert [(d.attrs["workload"], d.attrs["chunk"], d.attrs["lanes"],
             d.attrs["width"]) for d in ds] == [
        (w, c, 14 if c < 2 else 12, 14) for w in range(2) for c in range(3)]
    for d in ds:
        assert d.parent == study[0].id
        assert d.counts["lane_steps_run"] >= d.counts["lane_events"] > 0
        assert d.counts["lane_steps_run"] % (14 * SCAN_SEG) == 0
        assert [r.parent for r in recs if r.name == "repro.sweep.device"
                ].count(d.id) == 1
    assert sum(d.counts["lane_events"] for d in ds) == sum(
        _events(n, g.n_groups) for g in grids.values())

    obs.clear()
    pw = pack_workload(wls["w0"])
    ks37 = core.PAPER_SCALE_RATIOS
    m = run_window_oracle(pw, ks37, 300.0, 64, mode="chunked",
                          chunk_lanes=16)
    ds = _dispatches(obs.records())
    # 37 lanes: 3 chunks of width 13, the last with 11 real lanes
    assert [(d.attrs["chunk"], d.attrs["lanes"], d.attrs["width"])
            for d in ds] == [(0, 13, 13), (1, 13, 13), (2, 11, 13)]
    assert sum(d.counts["lane_events"] for d in ds) == _events(n, m.n_groups)
    assert all(d.counts["lane_steps_run"] >= d.counts["lane_events"]
               for d in ds)


@pytest.mark.parametrize("step_impl", ["xla", "pallas"])
def test_fused_study_records_one_dispatch(step_impl):
    n = 80
    wls = {f"w{i}": generate_workload(WorkloadParams(
        n_jobs=n, nodes=32, n_types=3, load=0.9, homogeneous=True, seed=i))
        for i in range(3)}
    cohort = core.group_workloads(wls, {w: np.float32 for w in wls})[0]
    obs.clear()
    grids = core.run_cohort_grid(cohort, ks=(0.5, 5.0, 500.0),
                                 s_props=(0.05, 0.5), mode="fused",
                                 step_impl=step_impl)
    ds = _dispatches(obs.records())
    assert len(ds) == 1
    assert ds[0].attrs == {"chunk": 0, "lanes": 18, "width": 18}
    events = sum(_events(n, g.n_groups) for g in grids.values())
    assert ds[0].counts["lane_events"] == events
    # whole segments of one loop over every lane (XLA step) or of one
    # loop per member (pallas step)
    steps = ds[0].counts["lane_steps_run"]
    assert steps >= events
    assert steps % ((18 if step_impl == "xla" else 6) * SCAN_SEG) == 0


def test_service_ticks_and_oracle_span():
    wl = generate_workload(WorkloadParams(n_jobs=600, nodes=64, load=0.9,
                                          homogeneous=True, seed=5))
    config = ServiceConfig(ks=core.PAPER_SCALE_RATIOS, window_jobs=200,
                           stride_jobs=200)
    obs.clear()
    out = run_service(wl, config)
    recs = obs.records()
    ticks = [r for r in recs if r.name == "repro.service.tick"]
    assert len(ticks) == out["n_ticks"] == 3
    stages = ["repro.service.signals", "repro.service.pack",
              "repro.service.oracle", "repro.service.score",
              "repro.service.decide"]
    for tick, tick_out in zip(ticks, out["ticks"]):
        kids = [r for r in recs if r.parent == tick.id]
        assert [r.name for r in kids] == stages
        oracle = kids[2]
        assert tick_out["oracle_ms"] == pytest.approx(
            (oracle.t1 - oracle.t0) * 1e-6)
        ds = [r for r in recs if r.parent == oracle.id]
        assert [d.name for d in ds] == ["repro.sweep.dispatch"]
        assert ds[0].attrs["lanes"] == 37


@pytest.mark.parametrize("step_impl", ["xla", "pallas"])
def test_engine_counts_the_segments_it_ran(step_impl):
    n = 60
    wl = generate_workload(WorkloadParams(n_jobs=n, nodes=16, n_types=3,
                                          load=0.9, homogeneous=True,
                                          seed=2))
    pw = pack_workload(wl)
    ring = resolve_ring(16, n)

    def run(k, s, seg=None, with_segments=False):
        return jax.jit(lambda k, s: simulate_packet_scan_lanes(
            pw, k, s, 16, ring=ring, seg=seg, step_impl=step_impl,
            with_segments=with_segments))(k, s)

    k = jnp.asarray([0.5, 4.0, 50.0], jnp.float32)
    s = jnp.full((3,), wl.init_time_for_proportion(0.1), jnp.float32)
    res, segs = jax.tree.map(np.asarray, run(k, s, with_segments=True))
    steps_run = k.shape[0] * int(segs.max()) * SCAN_SEG
    assert steps_run >= _events(n, res.n_groups)
    # one lane, one step per segment: it ran exactly its own events
    one, seg1 = jax.tree.map(np.asarray, run(k[:1], s[:1], seg=1,
                                             with_segments=True))
    assert int(seg1[0]) == n + 2 * int(one.n_groups[0])
    plain = jax.tree.map(np.asarray, run(k[:1], s[:1], seg=1))
    for f in plain._fields:
        np.testing.assert_array_equal(getattr(one, f), getattr(plain, f),
                                      err_msg=f)

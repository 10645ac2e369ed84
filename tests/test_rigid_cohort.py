"""The rigid baselines' cohort path (`run_cohort_baselines`): one program
per scheduler over stacked flows x init proportions, lane by lane equal to
the single-flow runs in both dtypes, with its spans and counters."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (RIGID_SCHEDULERS, efficiency_metrics, group_workloads,
                        pack_workload, precision, run_baselines,
                        run_cohort_baselines, simulate_rigid)
from repro.workload.lublin import WorkloadParams, generate_workload

S_PROPS = (0.05, 0.3, 0.5)


@pytest.fixture(scope="module")
def flows():
    return {f"homog{ld:.2f}": generate_workload(WorkloadParams(
                n_jobs=120, nodes=32, load=ld, homogeneous=True, seed=11 + i,
                daily_amplitude=0.3))
            for i, ld in enumerate((0.85, 0.95))}


def _single(wl, s_prop, scheduler, dtype):
    with precision.dtype_scope(dtype):
        pw = pack_workload(wl, dtype)
        m = int(wl.params.nodes)
        res, counts = simulate_rigid(pw, wl.init_time_for_proportion(s_prop),
                                     m, scheduler)
        metrics = efficiency_metrics(pw.submit, res, m, pw.t_last_submit)
        return jax.tree.map(np.asarray, (metrics, res.start_t, counts))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_cohort_equals_single_flow_runs(flows, dtype):
    cohort, = group_workloads(flows, dtype)
    runs = run_cohort_baselines(cohort, S_PROPS)
    assert set(runs) == set(flows)
    for name, wl in flows.items():
        assert set(runs[name]) == set(RIGID_SCHEDULERS)
        for sched, run in runs[name].items():
            assert run.start_t.shape == (len(S_PROPS), wl.n_jobs)
            for j, p in enumerate(S_PROPS):
                metrics, start_t, _ = _single(wl, p, sched, dtype)
                np.testing.assert_array_equal(run.start_t[j], start_t,
                                              err_msg=f"{name} {sched} {p}")
                for f in metrics._fields:
                    np.testing.assert_array_equal(
                        getattr(run.metrics, f)[j], getattr(metrics, f),
                        err_msg=f"{name} {sched} {p} {f}")
                assert bool(run.metrics.ok[j])
                assert not bool(run.metrics.budget_exhausted[j])


def test_run_baselines_is_the_one_member_call(flows):
    name, wl = next(iter(flows.items()))
    cohort, = group_workloads(flows, np.float32)
    runs = run_cohort_baselines(cohort, S_PROPS)[name]
    got = run_baselines(wl, s_props=S_PROPS)
    for sched in RIGID_SCHEDULERS:
        for f in got[sched]._fields:
            np.testing.assert_array_equal(getattr(got[sched], f),
                                          getattr(runs[sched].metrics, f))


def test_spans_and_counters(flows):
    """One `repro.baselines` span, a dispatch per scheduler under it with
    device stamps, and counters equal to the counts recomputed from the
    single-flow runs."""
    cohort, = group_workloads(flows, np.float32)
    obs.clear()
    run_cohort_baselines(cohort, S_PROPS, backfill_depth=64)
    recs = obs.records()
    unit, = [r for r in recs if r.name == "repro.baselines"]
    assert unit.attrs == {"workloads": 2, "s_props": 3,
                          "schedulers": "fcfs,backfill"}
    dispatches = {r.attrs["scheduler"]: r for r in recs
                  if r.name == "repro.sweep.dispatch"}
    assert set(dispatches) == set(RIGID_SCHEDULERS)
    for sched, d in dispatches.items():
        assert d.parent == unit.id
        assert any(r.name == "repro.sweep.device" and r.parent == d.id
                   for r in recs)
        counts = [_single(wl, p, sched, np.float32)[2]
                  for wl in flows.values() for p in S_PROPS]
        n_jobs = cohort.n_jobs
        # each job is submitted and completes once: one outer trip each
        assert all(int(c.trips) == 2 * n_jobs for c in counts)
        assert d.counts["lane_events"] == 2 * n_jobs * len(counts)
        assert d.counts["lane_steps_run"] == len(counts) * max(
            int(c.trips) for c in counts)
        assert d.counts["backfill_slots"] == 64 * sum(int(c.passes)
                                                      for c in counts)
        assert d.counts["backfill_candidates"] == sum(
            int(c.candidates) for c in counts)
        if sched == "backfill":
            assert 0 < d.counts["backfill_candidates"] \
                < d.counts["backfill_slots"]
        else:
            assert d.counts["backfill_slots"] == 0


def test_unknown_scheduler_is_refused(flows):
    cohort, = group_workloads(flows, np.float32)
    with pytest.raises(ValueError, match="conservative"):
        run_cohort_baselines(cohort, S_PROPS, schedulers=("conservative",))

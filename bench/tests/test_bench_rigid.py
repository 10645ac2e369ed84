"""The rigid baselines' plain reference (`reference_rigid`), the program
against it, the cell run whole on the CPU at a small size, and the readers
of the rigid dispatches' spans and counters."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import _paths
import gen
import reference
import reference_rigid
import run_cell
from repro import obs

HAND = ([0, 0, 1, 2, 3], [100, 10, 100, 500, 500], [6, 4, 8, 2, 2])


@pytest.mark.parametrize("scheduler, starts", [
    ("backfill", [0, 0, 100, 10, 200]), ("fcfs", [0, 0, 100, 100, 200])])
def test_reference_hand_case(scheduler, starts):
    """M = 10: the head (8 nodes) is reserved t = 100 with 2 extra nodes at
    t = 10; EASY backfills one 2-node job into them, not two."""
    out = reference_rigid.simulate(*HAND, 10, 0.0, scheduler)
    np.testing.assert_array_equal(out["start"], starts)
    assert out["ok"] and out["steps"] == 10


def _flows(n_jobs=400):
    return {f"homog{load:.2f}": gen.generate(
                n_jobs=n_jobs, nodes=100, load=load, homogeneous=True,
                daily_amplitude=0.3, seed=gen.seed_int(41, i))
            for i, load in enumerate((0.85, 0.95))}


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_program_starts_equal_reference(dtype):
    """Every job's start, in every run, equals the reference's rounded to
    the program's precision."""
    from repro.core import group_workloads, run_cohort_baselines
    common = run_cell.load_module("loops", "_common")
    flows = _flows()
    s_props = (0.05, 0.3, 0.5)
    cohort, = group_workloads(
        {n: common.program_workload(fl) for n, fl in flows.items()},
        np.dtype(dtype))
    runs = run_cohort_baselines(cohort, s_props)
    rnd = reference.rounder(dtype)
    for name, fl in flows.items():
        for sched, run in runs[name].items():
            for j, p in enumerate(s_props):
                want = reference_rigid.simulate(
                    fl["submit"], fl["runtime"], fl["nodes"], 100,
                    reference.init_time(fl["runtime"], p), sched, rnd=rnd)
                np.testing.assert_array_equal(
                    np.asarray(run.start_t[j], np.float64), want["start"],
                    err_msg=f"{name} {sched} {p}")
                assert want["ok"] and bool(run.metrics.ok[j])


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_correct_on_cpu(trace, tmp_path):
    """The whole cell at 300 jobs a flow: correct, whole studies of 36
    runs, and with ``--trace 1`` every per-layer metric of the cell."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(_paths.ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(_paths.BENCH, "run_cell.py"),
         "--workload", "homog-baselines", "--seed", str(2 ** 31 + 29),
         "--seconds", "0.5", "--trace", str(trace), "--allow-cpu",
         "--override", "config.flows.n_jobs=300"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out
    assert out["attempted"] % 36 == 0 and out["attempted"] > 0, out
    spec = json.load(open(os.path.join(_paths.ROOT, "BENCHMARK.json")))
    want = {m["name"] for m in run_cell.cell_metrics(
        spec, "homog-baselines", bool(trace))}
    assert set(out["metrics"]) == want, out
    if trace:
        assert want == {"dispatch_event_ns.baselines", "lane_fill.baselines",
                        "backfill_fill.baselines"}
        assert all(m["value"] is not None for m in out["metrics"].values())


R = obs.Record
MS = 1_000_000


def _unit(base, counts_fcfs, counts_bf):
    """A `repro.baselines` span with its two dispatches and their stamps."""
    return [R(base, None, "repro.baselines", base * MS, (base + 90) * MS,
              {}, {}),
            R(base + 1, base, "repro.sweep.dispatch", base * MS,
              (base + 1) * MS, {"scheduler": "fcfs"}, counts_fcfs),
            R(base + 2, base, "repro.sweep.dispatch", (base + 1) * MS,
              (base + 2) * MS, {"scheduler": "backfill"}, counts_bf),
            R(base + 3, base + 1, "repro.sweep.device", base * MS,
              (base + 10) * MS, {"device": 0}, {}),
            R(base + 4, base + 2, "repro.sweep.device", (base + 10) * MS,
              (base + 80) * MS, {"device": 0}, {})]


def _counts(events, steps, slots, cands):
    return {"lane_events": events, "lane_steps_run": steps,
            "backfill_slots": slots, "backfill_candidates": cands}


class _Loop:
    def __init__(self, n):
        self.done = [None] * n


def test_readers_skip_the_warm_up(monkeypatch):
    recs = (_unit(100, _counts(1, 1, 0, 0), _counts(1, 1, 1, 1))
            + _unit(200, _counts(100, 100, 0, 0), _counts(100, 100, 640, 64))
            + _unit(300, _counts(100, 100, 0, 0), _counts(100, 125, 640, 96)))
    monkeypatch.setattr(obs, "records", lambda timeout=60.0: list(recs))
    read = lambda m: run_cell.load_reader(m)({"loop": _Loop(2)})
    # device: (10 + 70) ms a unit, over 200 events a unit
    assert read("dispatch_event_ns.baselines") == pytest.approx(
        160 * MS / 400)
    assert read("lane_fill.baselines") == pytest.approx(400 / 425 * 100)
    assert read("backfill_fill.baselines") == pytest.approx(160 / 1280 * 100)


@pytest.mark.parametrize("metric", ["dispatch_event_ns.baselines",
                                    "lane_fill.baselines",
                                    "backfill_fill.baselines"])
def test_readers_without_records_read_nothing(metric, monkeypatch):
    monkeypatch.setattr(obs, "records", lambda timeout=60.0: [])
    assert run_cell.load_reader(metric)({"loop": _Loop(1)}) is None
    # a program without the recorder
    monkeypatch.delattr(sys.modules["repro"], "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert run_cell.load_reader(metric)({"loop": _Loop(1)}) is None

"""Whole benchmark runs on the CPU at a small size, with the timed path
broken underneath (`faulty_run.py`): the unbroken run is correct, and each
fault the cell can have makes `correct` come out false."""
import json
import os
import subprocess
import sys

import pytest

import _paths

SMALL_STUDY = ["--override", "config.flows.n_jobs=300"]
# 300 jobs on 500 nodes run a group ring of 300
SMALL_HETERO = SMALL_STUDY + ["--override", "config.ring=300"]
SMALL_SERVICE = ["--override", "mix.scenario_jobs=1400",
                 "--override", "mix.scenario_segments=7",
                 "--override", "mix.window_jobs=200",
                 "--override", "mix.stride_jobs=100",
                 "--override", "mix.check_units=1"]
CASES = {
    "homog-study": (["none", "state_unchanged", "half_batch",
                     "answer_altered", "lane_shift", "chunk_swap"],
                    SMALL_STUDY, 1),
    "hetero-study": (["none", "lane_shift", "chunk_swap"], SMALL_HETERO, 1),
    "homog-service": (["none", "state_unchanged", "half_batch",
                       "answer_altered", "curve_raised"], SMALL_SERVICE, 1),
    "homog-study-4chip": (["none", "no_exchange", "lane_shift"],
                          SMALL_STUDY, 4),
}


@pytest.mark.parametrize("cell", sorted(CASES))
def test_faults_make_runs_incorrect(cell, tmp_path):
    faults, small, n_dev = CASES[cell]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}")
    cmd = [sys.executable, os.path.join(_paths.TESTS, "faulty_run.py"),
           ",".join(faults), "--", "--workload", cell,
           "--seed", str(2 ** 31 + 17), "--seconds", "0.5"] + small
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = {}
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            got[rec["fault"]] = rec
    assert set(got) == set(faults), proc.stdout[-3000:]
    assert got["none"]["correct"] is True, got["none"]
    for fault in faults[1:]:
        assert got[fault]["correct"] is False, (fault, got[fault])

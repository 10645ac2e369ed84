"""Whole runs of the rigid-baselines cell on the CPU at a small size, with
the timed path broken underneath (`faulty_rigid_run.py`): the unbroken run
is correct; the bfloat16 control and each planted fault (a lane shift,
the schedulers swapped, the EASY pass that never spends its extra nodes)
come out incorrect against the cell's limits."""
import json
import os
import subprocess
import sys

import pytest

import _paths

FAULTS = ["none", "control", "lane_shift", "sched_swap", "fixed_extra"]


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_paths.TESTS, "faulty_rigid_run.py"),
         ",".join(FAULTS), "--", "--workload", "homog-baselines",
         "--seed", str(2 ** 31 + 17), "--seconds", "0.5",
         "--override", "config.flows.n_jobs=300"],
        env=env, capture_output=True, text=True, timeout=900,
        cwd=str(tmp_path_factory.mktemp("faults")))
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = {}
    for line in proc.stdout.splitlines():
        if line.startswith('{"fault"'):
            rec = json.loads(line)
            got[rec["fault"]] = rec
    assert set(got) == set(FAULTS), proc.stdout[-3000:]
    return got


@pytest.mark.parametrize("fault", FAULTS)
def test_only_the_unbroken_run_is_correct(fault, readings):
    rec = readings[fault]
    assert rec["failed"] == 0, rec
    assert rec["correct"] is (fault == "none"), rec

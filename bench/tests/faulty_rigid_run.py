"""Drive whole runs of a rigid-baselines cell with the timed path broken
underneath.

    python3 bench/tests/faulty_rigid_run.py <fault>[,<fault>...] -- \\
        <run_cell args>

For each fault (``none`` is the unbroken run) the program is broken in that
way and the rest of the run is the harness's own (`run_cell.run`, without
its look for a chip). One JSON line per fault: ``{"fault", "correct",
"failed", "checks"}``.

* ``control``: the unbroken run, with the reference rounded to the
  configuration's ``control_dtype`` checked in the program's place;
* ``lane_shift``: each flow's runs land one init proportion off (rolled by
  one along the init-proportion axis);
* ``sched_swap``: FCFS's runs come back as EASY's, and EASY's as FCFS's;
* ``fixed_extra``: the EASY pass as it stood before its repair: the extra
  nodes are worked out once a pass and never spent, so two backfilled jobs
  can take the same spare nodes and delay the head.
"""
import json
import sys

import _paths  # noqa: F401
import numpy as np

import reference
import run_cell

FAULTS = ("none", "control", "lane_shift", "sched_swap", "fixed_extra")


def _shifted(runs):
    from repro.core import BaselineRun
    return {name: {sched: BaselineRun(
                type(run.metrics)(*(np.roll(np.asarray(x), 1, axis=0)
                                    for x in run.metrics)),
                np.roll(run.start_t, 1, axis=0))
                   for sched, run in by.items()}
            for name, by in runs.items()}


def _swapped(runs):
    return {name: {"fcfs": by["backfill"], "backfill": by["fcfs"]}
            for name, by in runs.items()}


def install(fault: str, cfg: dict):
    """Break the program (or the check) for `fault`; returns an undo."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    import jax
    from repro import core
    from repro.core import schedulers
    real_run, real_spend = core.run_cohort_baselines, schedulers._spend_extra
    Loop = run_cell.load_loop("baselines")
    real_check = Loop.check

    if fault in ("lane_shift", "sched_swap"):
        alter = _shifted if fault == "lane_shift" else _swapped

        def run(cohort, *a, **kw):
            return alter(real_run(cohort, *a, **kw))
        core.run_cohort_baselines = run
    elif fault == "fixed_extra":
        schedulers._spend_extra = lambda extra, m, started, ends_before: \
            extra
        jax.clear_caches()          # retrace the EASY pass without it
    elif fault == "control":
        rnd = reference.rounder(cfg["control_dtype"])
        Loop.check = lambda self, rng, n, rnd_=None: real_check(
            self, rng, n, rnd=rnd)

    def undo():
        core.run_cohort_baselines = real_run
        Loop.check = real_check
        if schedulers._spend_extra is not real_spend:
            schedulers._spend_extra = real_spend
            jax.clear_caches()
    return undo


def main(argv):
    faults, args = argv[0].split(","), argv[argv.index("--") + 1:]
    parsed = run_cell.parse_args(args + ["--allow-cpu"])
    _, _, cfg, _, _ = run_cell.load_cell(parsed.workload, parsed.override)
    for fault in faults:
        undo = install(fault, cfg)
        try:
            out, checks = run_cell.run(parsed)
        finally:
            undo()
        print(json.dumps({"fault": fault, "correct": out["correct"],
                          "failed": out["failed"], "checks": checks}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])

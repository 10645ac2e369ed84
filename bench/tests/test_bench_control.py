"""The lower-precision control fails the cell's limits: the reference,
computed with every result rounded to the precision below the one the
configuration states, put in the program's place. A smaller size than the
cells' (the chip runs are recorded in PERF.md), the same limits."""
import json
import os

import numpy as np
import pytest

import _paths
import check
import gen
import reference
import run_cell

CELLS = {"homog-study": "paper-homog", "hetero-study": "paper-hetero"}
#: flows long enough for float32's rounding to move the heterogeneous
#: schedules past the limit (at 1500 jobs it stays under it)
N_JOBS = {"homog-study": 1500, "hetero-study": 3000}
LANES = {"homog-study": 3, "hetero-study": 16}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_is_not_correct(cell):
    cfg = json.load(open(os.path.join(_paths.BENCH, "configs",
                                      CELLS[cell] + ".json")))
    limits = json.load(open(os.path.join(_paths.BENCH, "limits",
                                         cell + ".json")))
    f = cfg["flows"]
    rnd = reference.rounder(cfg["control_dtype"])
    rng = np.random.default_rng(2 ** 31 + 5)
    pairs = []
    for i, load in enumerate(f["loads"]):
        fl = gen.generate(n_jobs=N_JOBS[cell], nodes=f["nodes"], load=load,
                          homogeneous=f["homogeneous"],
                          daily_amplitude=f["daily_amplitude"],
                          seed=gen.seed_int(2 ** 31 + 5, i))
        for _ in range(LANES[cell]):
            k = float(rng.choice(cfg["scale_ratios"]))
            s = reference.init_time(fl["runtime"],
                                    float(rng.choice(cfg["init_props"])))
            args = (fl["submit"], fl["work"], fl["jtype"], fl["n_types"],
                    f["nodes"], k, s)
            pairs.append((reference.simulate(*args, rnd=rnd),
                          reference.simulate(*args)))
    got = check.lane_numbers(pairs)
    assert any(got[k] > lim["limit"] for k, lim in limits.items()), got


def test_service_control_is_not_correct():
    """The bfloat16 control of the service's tuning curves, over every tick
    of one small drift scenario, against the service cell's limits."""
    cfg = json.load(open(os.path.join(_paths.BENCH, "configs",
                                      "paper-homog.json")))
    mix = json.load(open(os.path.join(_paths.BENCH, "traffic",
                                      "service.json")))
    limits = json.load(open(os.path.join(_paths.BENCH, "limits",
                                         "homog-service.json")))
    mix.update(scenario_jobs=1400, scenario_segments=7, window_jobs=200,
               stride_jobs=100)
    loop = run_cell.load_loop("service")(cfg, mix, 2 ** 31 + 5)
    rec = {"flow": next(iter(loop.scenarios(0).values()))}
    ref = [t["curve"] for t in loop.reference_ticks(rec)]
    ctl = [t["curve"] for t in loop.reference_ticks(
        rec, rnd=reference.rounder(cfg["control_dtype"]))]
    got = check.tick_numbers([(ctl, loop.replay(ctl), ref,
                               loop.replay(ref))])
    assert any(got[k] > lim["limit"] for k, lim in limits.items()), got

"""The lane-event count read from a lane's outputs equals the step count of
the benchmark's own plain reference, with and without requeued work."""
import numpy as np
import pytest

import _paths  # noqa: F401
import gen
import reference


def _flow(n=300, nodes=100, homogeneous=True):
    return gen.generate(n_jobs=n, nodes=nodes, homogeneous=homogeneous,
                        seed=gen.seed_int(7, n, nodes))


@pytest.mark.parametrize("k", [0.1, 2.0, 100.0])
@pytest.mark.parametrize("faults", [False, True])
def test_lane_events_equal_reference_steps(k, faults):
    fl = _flow()
    s = reference.init_time(fl["runtime"], 0.2)
    fail = None
    if faults:
        # every third group is credited with 40% of its work; the rest is
        # requeued and forms further groups
        fail = lambda g: 0.4 if g % 3 == 0 else 1.0
    out = reference.simulate(fl["submit"], fl["work"], fl["jtype"],
                             fl["n_types"], fl["nodes_total"], k, s,
                             fail=fail)
    assert out["ok"]
    assert out["steps"] == reference.lane_events(len(fl["submit"]),
                                                 out["n_groups"])
    if faults:
        plain = reference.simulate(fl["submit"], fl["work"], fl["jtype"],
                                   fl["n_types"], fl["nodes_total"], k, s)
        assert out["n_groups"] > plain["n_groups"]


def test_paper_example_group_width():
    # paper Fig. 3: s = 1 min, 4 node-minutes of work; k = 0.5 -> 8 nodes,
    # k = 1 -> 4, k = 2 -> 2, k = 4 -> 1; the group runs s + work / m. A
    # second job long after sets the metric window to 10000 s.
    for k, m in ((0.5, 8), (1.0, 4), (2.0, 2), (4.0, 1)):
        out = reference.simulate(np.array([0.0, 1e4]),
                                 np.array([240.0, 1.0]), np.array([0, 0]),
                                 1, 16, k, 60.0)
        assert out["n_groups"] == 2
        assert out["full_util"] == pytest.approx(m * (60 + 240 / m)
                                                 / (16 * 1e4))
        assert out["useful_util"] == pytest.approx(240 / (16 * 1e4))


def test_lower_precision_rounds():
    r = reference.rounder("bfloat16")
    assert r(1.0 + 2 ** -10) == 1.0
    assert reference.rounder("float64") is None

"""The copied generators reproduce the program's flows as they stood when
the benchmark was defined (digests recorded in `golden_digests.json`)."""
import json
import os

import pytest

import _paths
import gen

GOLDEN = json.load(open(os.path.join(_paths.BENCH, "golden_digests.json")))


@pytest.mark.parametrize("load", [0.85, 0.90, 0.95])
def test_paper_flows(load):
    want = GOLDEN["paper_workloads_seed0"]
    assert gen.digest(gen.generate(nodes=500, load=load, seed=0)) == \
        want[f"hetero{load:.2f}"]
    assert gen.digest(gen.generate(nodes=100, load=load, homogeneous=True,
                                   seed=1, daily_amplitude=0.3)) == \
        want[f"homog{load:.2f}"]


def test_drift_scenarios():
    want = GOLDEN["drift_scenarios_seed0"]
    got = {n: gen.digest(f) for n, f in gen.drift_scenarios().items()}
    assert got == want


def test_seeds_are_large_and_distinct():
    big = 2 ** 31 + 12345
    seeds = {gen.seed_int(big, i, j) for i in range(3) for j in range(3)}
    assert len(seeds) == 9
    a = gen.generate(n_jobs=50, seed=gen.seed_int(big, 0, 0))
    b = gen.generate(n_jobs=50, seed=gen.seed_int(big, 0, 0))
    assert gen.digest(a) == gen.digest(b)

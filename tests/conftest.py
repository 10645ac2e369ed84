import os

# Tests run on the CPU backend (Pallas kernels in interpret mode); the chip
# is exercised by chip_smoke.py, and tests/test_tpu_compile.py compiles for
# a described TPU without one.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

from repro.workload.lublin import Workload, WorkloadParams, generate_workload


def make_workload(submit, runtime, nodes, jtype, n_types, m_nodes) -> Workload:
    """Hand-constructed Workload for behaviour/equivalence tests."""
    submit = np.asarray(submit, np.float64)
    runtime = np.asarray(runtime, np.float64)
    nodes = np.asarray(nodes, np.int64)
    jtype = np.asarray(jtype, np.int64)
    order = np.argsort(submit, kind="stable")
    p = WorkloadParams(n_jobs=len(submit), nodes=m_nodes, n_types=n_types,
                       horizon=float(submit.max()) if len(submit) else 1.0)
    return Workload(submit=submit[order], runtime=runtime[order],
                    nodes=nodes[order], work=(runtime * nodes)[order],
                    jtype=jtype[order], params=p)


@pytest.fixture(scope="session")
def small_workload():
    return generate_workload(WorkloadParams(
        n_jobs=300, nodes=64, load=0.9, homogeneous=True, seed=7))


@pytest.fixture(scope="session")
def hetero_workload():
    return generate_workload(WorkloadParams(
        n_jobs=300, nodes=128, load=0.85, homogeneous=False, seed=3))

"""Helpers that the loop kinds share (not a loop kind itself)."""
from __future__ import annotations

import reference


def span(name):
    """A host span `bench.<name>` in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


def program_workload(flow: dict):
    """The program's `Workload` for one generated flow."""
    from repro.workload.lublin import Workload, WorkloadParams
    p = flow["params"]
    params = WorkloadParams(
        n_jobs=len(flow["submit"]), horizon=float(p["horizon"]),
        n_types=int(flow["n_types"]), nodes=int(flow["nodes_total"]),
        load=float(p["load"]), homogeneous=bool(p["homogeneous"]),
        daily_amplitude=float(p["daily_amplitude"]),
        homog_shrink=float(p["homog_shrink"]))
    return Workload(submit=flow["submit"], runtime=flow["runtime"],
                    nodes=flow["nodes"], work=flow["work"],
                    jtype=flow["jtype"], params=params)


def simulate(cfg: dict, submit, work, jtype, n_types: int, k: float,
             s: float, rnd=None) -> dict:
    """One experiment of the plain reference on the configuration's machine,
    group ring and queue-weight policy."""
    pol = cfg["policy"]
    return reference.simulate(submit, work, jtype, n_types,
                              cfg["flows"]["nodes"], k, s,
                              slots=cfg["ring"], rnd=rnd,
                              t_max=pol["t_max_s"],
                              priority=pol["priority"])


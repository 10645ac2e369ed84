"""Run the paper's full 1332-experiment grid and persist results.

6 workflows x 37 scale ratios x 6 init proportions, exactly the study of
paper §6-7.  Results land in benchmarks/results/paper_grid.json and are read
by the per-figure benchmark functions in benchmarks/run.py.

Cohort execution: the 6 workflows are no longer iterated sequentially in
Python. `repro.core.cohort.group_workloads` partitions them by compile-time
statics — under the default precision policy below, exactly two cohorts:
3 heterogeneous flows (M=500, float64) and 3 homogeneous flows (M=100,
float32) — and `run_cohort_grid` runs each cohort's whole W x 222-lane
study as one batched program family (666 lanes per cohort instead of three
sequential 222-lane sweeps). Per-workload results are unstacked back into
the same paper_grid.json schema as before, so the figure code in
benchmarks/run.py is untouched; per-cohort timing and the cohort sweep plan
are persisted alongside (``cohorts`` / ``sweep_plan`` keys).

Precision policy: the PR-2 tolerance study
(benchmarks/results/BENCH_dtype.json) found 77-83% of paper-grid cells on
5000-job HETEROGENEOUS flows schedule differently in float32 vs float64
(near-tie cascades), while homogeneous flows stay at rounding level. Each
workload therefore defaults to the cheapest dtype that is decision-stable:
float64 for heterogeneous flows, float32 for homogeneous ones. ``--float64``
forces everything up, ``--float32`` is the escape hatch that forces
everything down (accepting the documented schedule flips); the per-workload
decision and its reason are persisted in the grid provenance either way.

``--workloads name1,name2`` restricts the study to a subset of the 6 flows
(smoke runs and bisection then pay only for the workloads under test).

``--chaos`` re-runs the study as a fault sweep: every (workload, k, s)
cell is crossed with a chaos lane axis of MTBF x checkpoint-period x
straggler-factor cells (`chaos_grid_config`), the grids gain the fault
metrics (lost_work / failures / straggler_kills / requeues /
requeued_jobs / budget_exhausted) with a trailing chaos axis, and
results land in ``paper_chaos_grid.json`` so the zero-chaos study file
stays untouched. A ``figure_scale_ratio_vs_faults`` block summarizes
the study's question — how the avg_wait-optimal scale ratio k* and its
5% plateau shift with fault rate and checkpoint cadence — per
(workload, init proportion, chaos cell), ready for figure code.
Baselines are skipped under chaos — FCFS/backfill carry no fault
semantics to compare against.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (PAPER_INIT_PROPS, PAPER_SCALE_RATIOS, ChaosConfig,
                        chaos_axis_len, group_workloads,
                        run_cohort_baselines, run_cohort_grid, sweep_plan)
from repro.workload.lublin import paper_workloads

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
GRID_PATH = os.path.join(RESULTS_DIR, "paper_grid.json")
CHAOS_GRID_PATH = os.path.join(RESULTS_DIR, "paper_chaos_grid.json")

GRID_FIELDS = ("avg_wait", "med_wait", "avg_qlen", "full_util",
               "useful_util", "avg_run_wait", "n_groups", "ok")
CHAOS_FIELDS = ("lost_work", "failures", "straggler_kills", "requeues",
                "requeued_jobs", "budget_exhausted")
BASELINE_FIELDS = ("avg_wait", "med_wait", "full_util", "useful_util")

# a cell's k belongs to the optimal plateau if its avg_wait is within
# this relative tolerance of the best k's avg_wait (paper §7 reads the
# tuning curves as flat-bottomed valleys, not single sharp minima)
PLATEAU_RTOL = 0.05

# the --chaos study axes: every combination becomes one chaos lane cell
CHAOS_MTBF_HOURS = (50.0, 200.0)
CHAOS_CKPT_PERIODS = (120.0, 600.0)
CHAOS_STRAGGLER_FACTORS = (1.5, 4.0)


def chaos_grid_config(seed: int = 0) -> ChaosConfig:
    """The fault sweep's chaos lane axis: MTBF x ckpt x straggler factor.

    Scalar straggler probability/deadline broadcast across the cells; the
    factor axis spans "stretch absorbed within the 2x deadline" (1.5) and
    "stretch that triggers a deadline kill" (4.0), so the sweep exercises
    both straggler outcomes.
    """
    mtbf, ckpt, factor = np.meshgrid(
        np.asarray(CHAOS_MTBF_HOURS), np.asarray(CHAOS_CKPT_PERIODS),
        np.asarray(CHAOS_STRAGGLER_FACTORS), indexing="ij")
    return ChaosConfig(mtbf_chip_hours=mtbf.ravel(),
                       ckpt_period=ckpt.ravel(),
                       straggler_prob=0.1,
                       straggler_factor=factor.ravel(),
                       straggler_deadline=2.0, seed=seed)


def chaos_figure_data(out: dict) -> dict:
    """The scale-ratio-under-faults figure block, from a chaos-study dict.

    For every (workload, init proportion, chaos cell): the scale ratio
    minimizing avg_wait (``best_k``), its wait, and the lowest/highest k
    whose avg_wait stays within `PLATEAU_RTOL` of that minimum — the
    flat-bottomed tuning valley the paper reads optima from. Lists are
    indexed ``[init_prop][chaos_cell]``; the chaos-cell parameter axes
    are echoed so figure code needs no second file. Cells whose schedule
    was truncated (``ok`` False) are excluded from the minimization.
    """
    ks = np.asarray(out["scale_ratios"], np.float64)
    cells = out["chaos_cells"]
    fig = {"plateau_rtol": PLATEAU_RTOL,
           "mtbf_chip_hours": cells["mtbf_chip_hours"],
           "ckpt_period": cells["ckpt_period"],
           "straggler_factor": cells["straggler_factor"],
           "workloads": {}}
    n_k = len(ks)
    for name, grids in out["workloads"].items():
        aw = np.asarray(grids["avg_wait"], np.float64)      # [K, S, C]
        ok = np.asarray(grids["ok"], bool)
        aw = np.where(ok, aw, np.inf)
        best_wait = np.min(aw, axis=0)                      # [S, C]
        within = np.isfinite(aw) & (aw <= best_wait * (1.0 + PLATEAU_RTOL))
        k_idx = np.arange(n_k)[:, None, None]
        lo = np.minimum(np.min(np.where(within, k_idx, n_k), axis=0),
                        n_k - 1)
        hi = np.maximum(np.max(np.where(within, k_idx, -1), axis=0), 0)
        fig["workloads"][name] = {
            "best_k": ks[np.argmin(aw, axis=0)].tolist(),
            "best_avg_wait": np.where(np.isfinite(best_wait), best_wait,
                                      -1.0).tolist(),
            "plateau_k_lo": ks[lo].tolist(),
            "plateau_k_hi": ks[hi].tolist()}
    return fig


def workload_dtype(wl, force_dtype=None) -> tuple[np.dtype, str]:
    """The per-workload precision decision and why it was made."""
    if force_dtype is not None:
        return np.dtype(force_dtype), "forced by flag"
    if wl.params.homogeneous:
        return np.dtype(np.float32), (
            "homogeneous flow: float32 matches float64 to rounding level "
            "(BENCH_dtype.json)")
    return np.dtype(np.float64), (
        "heterogeneous flow: 77-83% of float32 cells flip schedules "
        "(BENCH_dtype.json near-tie cascades)")


def select_workloads(flows: dict, names) -> dict:
    """Subset `flows` to the requested names, preserving study order."""
    names = [n.strip() for n in names if n.strip()]
    unknown = [n for n in names if n not in flows]
    if unknown:
        raise ValueError(f"unknown workloads {unknown}; "
                         f"available: {sorted(flows)}")
    return {name: flows[name] for name in flows if name in names}


def run_full_grid(n_jobs: int | None = None, seed: int = 0,
                  dtype=None, mode: str = "auto",
                  workloads=None, chaos: ChaosConfig | None = None) -> dict:
    """n_jobs=None -> the paper's 5000; smaller for smoke runs.

    ``dtype=None`` (default) applies the per-workload policy of
    `workload_dtype`: float64 for heterogeneous flows, float32 for
    homogeneous ones. Passing a concrete dtype forces it for every
    workload. ``workloads`` (iterable of names) restricts the study to a
    subset of the 6 flows.

    The flows are grouped into same-static cohorts and each cohort runs as
    one batched study (`run_cohort_grid`); results are unstacked into the
    per-workload schema the figure code reads, and the chosen dtypes (with
    reasons), per-cohort sweep plans, and per-cohort timing are persisted
    so downstream comparisons know exactly what produced them.
    """
    flows = paper_workloads(seed=seed)
    if workloads is not None:
        flows = select_workloads(flows, list(workloads))
    if n_jobs is not None:
        import dataclasses
        from repro.workload.lublin import generate_workload
        flows = {name: generate_workload(dataclasses.replace(
            wl.params, n_jobs=n_jobs)) for name, wl in flows.items()}

    C = chaos_axis_len(chaos) if chaos is not None else 1
    n_grid = len(PAPER_SCALE_RATIOS) * len(PAPER_INIT_PROPS)
    n_lanes = n_grid * C
    grid_fields = GRID_FIELDS + (CHAOS_FIELDS if chaos is not None else ())
    decisions = {name: workload_dtype(wl, dtype) for name, wl in flows.items()}
    cohorts = group_workloads(flows, {name: d
                                      for name, (d, _) in decisions.items()},
                              chaos=chaos)
    out = {"scale_ratios": list(PAPER_SCALE_RATIOS),
           "init_props": list(PAPER_INIT_PROPS),
           "dtype": {name: d.name for name, (d, _) in decisions.items()},
           "dtype_reason": {name: why for name, (_, why) in decisions.items()},
           "sweep_plan": {}, "cohorts": {},
           "workload_digests": {name: wl.golden_digest()
                                for name, wl in flows.items()},
           "workloads": {}, "baselines": {}, "timing": {}}
    if chaos is not None:
        # per-cell parameter values along the trailing chaos axis of every
        # grid field (seed/requeue bound are in each cohort's sweep_plan)
        out["chaos_cells"] = {
            "axis_len": C,
            **{f: np.broadcast_to(np.asarray(getattr(chaos, f), np.float64),
                                  (C,)).tolist()
               for f in ("mtbf_chip_hours", "ckpt_period", "straggler_prob",
                         "straggler_factor", "straggler_deadline")}}

    for cohort in cohorts:
        w = cohort.n_workloads
        t0 = time.time()
        # run_cohort_grid returns host numpy, but block explicitly so the
        # recorded wall clock measures completed compute, not dispatch,
        # even if the unstacking path ever returns device arrays again.
        grids = jax.block_until_ready(
            run_cohort_grid(cohort, mode=mode, chaos=chaos))
        dt = time.time() - t0
        out["sweep_plan"][cohort.label] = sweep_plan(mode, n_grid, w,
                                                    chaos=chaos)
        out["cohorts"][cohort.label] = {
            "workloads": list(cohort.names), "dtype": cohort.dtype.name,
            "m_nodes": cohort.m_nodes, "n_jobs": cohort.n_jobs,
            "seconds": dt, "experiments": w * n_lanes,
            "sec_per_experiment": dt / (w * n_lanes)}
        for name in cohort.names:
            out["workloads"][name] = {
                f: np.asarray(getattr(grids[name], f)).tolist()
                for f in grid_fields}
            out["timing"][name] = {
                "seconds": dt / w, "experiments": n_lanes,
                "sec_per_experiment": dt / (w * n_lanes),
                "cohort": cohort.label}
        print(f"[paper_sweep] cohort {cohort.label} "
              f"({', '.join(cohort.names)}): {w * n_lanes} experiments in "
              f"{dt:.1f}s ({dt / (w * n_lanes) * 1e3:.1f} ms/experiment, "
              f"{cohort.dtype.name})", flush=True)

    if chaos is not None:
        out["figure_scale_ratio_vs_faults"] = chaos_figure_data(out)
    if chaos is None:
        # the rigid baselines, one program per scheduler and cohort
        for cohort in cohorts:
            t0 = time.time()
            runs = run_cohort_baselines(cohort)
            dt = time.time() - t0
            for name in cohort.names:
                out["timing"][name]["baseline_seconds"] = \
                    dt / cohort.n_workloads
                out["baselines"][name] = {
                    alg: {f: np.asarray(getattr(run.metrics, f)).tolist()
                          for f in BASELINE_FIELDS}
                    for alg, run in runs[name].items()}
    return out


def main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    prec = ap.add_mutually_exclusive_group()
    prec.add_argument("--float64", action="store_true",
                      help="force float64 for ALL workloads (default: only "
                           "heterogeneous flows run float64)")
    prec.add_argument("--float32", action="store_true",
                      help="escape hatch: force float32 for ALL workloads, "
                           "accepting the documented hetero-flow schedule "
                           "flips (BENCH_dtype.json)")
    ap.add_argument("--mode", default="auto",
                    choices=("auto", "seq", "chunked", "fused"),
                    help="cohort dispatch layout (the legacy vmap_k/vmap_s "
                         "layouts have no cohort form; use run_packet_grid "
                         "directly for those A/Bs)")
    ap.add_argument("--workloads", default=None, metavar="NAME1,NAME2",
                    help="run only these flows (comma-separated subset of "
                         "the 6 paper workflows), e.g. "
                         "--workloads homog0.85,hetero0.85")
    ap.add_argument("--chaos", action="store_true",
                    help="cross the study with the fault-parameter grid "
                         "(MTBF x ckpt period x straggler factor, "
                         "chaos_grid_config) and write paper_chaos_grid.json "
                         "instead of the zero-chaos study file")
    ap.add_argument("--chaos-seed", type=int, default=0, metavar="SEED",
                    help="fault-stream seed for --chaos (default 0)")
    ap.add_argument("--n-jobs", type=int, default=None, metavar="N",
                    help="jobs per workload (default: the paper's 5000; "
                         "smaller for smoke/CI runs)")
    args = ap.parse_args()
    enable_compile_cache()
    dtype = (np.float64 if args.float64
             else np.float32 if args.float32 else None)
    names = args.workloads.split(",") if args.workloads else None
    chaos = chaos_grid_config(seed=args.chaos_seed) if args.chaos else None
    out_path = CHAOS_GRID_PATH if args.chaos else GRID_PATH
    os.makedirs(RESULTS_DIR, exist_ok=True)
    t0 = time.time()
    res = run_full_grid(n_jobs=args.n_jobs, dtype=dtype, mode=args.mode,
                        workloads=names, chaos=chaos)
    res["total_seconds"] = time.time() - t0
    with open(out_path, "w") as f:
        json.dump(res, f)
    if chaos is not None:
        fig = res["figure_scale_ratio_vs_faults"]
        for name, d in fig["workloads"].items():
            b = np.asarray(d["best_k"])
            print(f"[paper_sweep]   {name}: avg_wait-optimal k spans "
                  f"{b.min():g}..{b.max():g} across "
                  f"{len(fig['mtbf_chip_hours'])} fault cells "
                  f"x {b.shape[0]} init props")
    n = sum(t["experiments"] for t in res["timing"].values())
    n_bl = 2 * len(res["baselines"])
    print(f"[paper_sweep] total: {n} Packet experiments in "
          f"{len(res['cohorts'])} cohort stud(ies) (+{n_bl} baseline runs) "
          f"in {res['total_seconds']:.1f}s -> {out_path}")


if __name__ == "__main__":
    main()

"""Paper-experiment driver: run the Packet DES over a (k x S) grid.

  PYTHONPATH=src python -m repro.launch.sim --workload homog0.85 \\
      --init-prop 0.05 --jobs 5000
prints the scale-ratio sweep for one workload (paper Figs. 5-14), plus the
plateau threshold the paper's method hands the JMS administrator.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (PAPER_SCALE_RATIOS, plateau_threshold,
                        run_baselines, run_packet_grid)
from repro.workload.lublin import (WorkloadParams, generate_workload,
                                   paper_workloads)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="homog0.85",
                    help="hetero|homog + load, e.g. homog0.90")
    ap.add_argument("--jobs", type=int, default=5000)
    ap.add_argument("--init-prop", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--baselines", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    homog = args.workload.startswith("homog")
    load = float(args.workload[-4:])
    wl = generate_workload(WorkloadParams(
        n_jobs=args.jobs, nodes=100 if homog else 500, load=load,
        homogeneous=homog, seed=args.seed + (1 if homog else 0)))
    print(f"[sim] workload {args.workload}: {wl.n_jobs} jobs, "
          f"calculated load {wl.calculated_load():.3f}, "
          f"M={wl.params.nodes}")

    grid = run_packet_grid(wl, s_props=[args.init_prop])
    ks = np.asarray(PAPER_SCALE_RATIOS)
    aw = np.asarray(grid.avg_wait)[:, 0]
    mw = np.asarray(grid.med_wait)[:, 0]
    fu = np.asarray(grid.full_util)[:, 0]
    uu = np.asarray(grid.useful_util)[:, 0]
    print(f"{'k':>8} {'avg_wait':>10} {'med_wait':>10} "
          f"{'full_util':>9} {'useful':>7}")
    for i, k in enumerate(ks):
        print(f"{k:8.1f} {aw[i]:10.1f} {mw[i]:10.1f} {fu[i]:9.3f} "
              f"{uu[i]:7.3f}")
    thr = plateau_threshold(ks, aw)
    print(f"[sim] queue-time plateau threshold: k >= {thr.threshold} "
          f"(plateau {thr.plateau:.1f}s)")
    if args.baselines:
        bl = run_baselines(wl, s_props=[args.init_prop])
        for name, m in bl.items():
            print(f"[sim] baseline {name}: avg_wait="
                  f"{float(np.asarray(m.avg_wait)[0]):.1f}s "
                  f"useful={float(np.asarray(m.useful_util)[0]):.3f}")


if __name__ == "__main__":
    main()

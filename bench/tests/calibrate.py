"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/tests/calibrate.py --workload <cell> --seeds <a,b,...> \\
        [--fault-seeds <c,d,...>] [--faults <fault,...>] [--out PATH]

One process, one warm-up: for each seed, the cell's loop runs as many
units as its traced window holds (`trace_units`), and the loop's own check compares them with the plain reference
twice: once with the program's answers (the lower reading) and once with
the reference rounded to the configuration's `control_dtype` in their
place (the control, the upper reading). For each fault seed, each fault of
`faulty_run.py` is planted under the timed path and read the same way.
One JSON line per reading, with the compared numbers, the quantiles of
the per-answer gaps and, for studies, each sampled experiment's answers
and the reference's. Runs on the chip; `--allow-cpu` and `--override` as
in `run_cell.py`, for rehearsals.
"""
import argparse
import json
import os
import sys
import time

import _paths  # noqa: F401
import numpy as np

import faulty_run
import run_cell


def _gap_quartiles(gaps):
    if not gaps:
        return None
    return [float(q) for q in np.quantile(np.asarray(gaps, np.float64),
                                          [0.0, 0.25, 0.5, 0.75, 0.9, 1.0])]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--override", action="append", default=[])
    args = ap.parse_args(argv)

    spec, cell, cfg, mix, limits = run_cell.load_cell(args.workload,
                                                      args.override)
    os.makedirs(run_cell.CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run_cell.CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", run_cell.CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = run_cell.device_info(jax, int(cell["chips"]), args.allow_cpu)
    import reference

    Loop = run_cell.load_loop(mix["loop"])
    units = int(mix["trace_units"])
    rnd = reference.rounder(cfg["control_dtype"])
    out = open(args.out, "w") if args.out else None
    Loop(cfg, mix, 0).warm_up()

    def emit(rec):
        line = json.dumps(dict(rec, cell=args.workload, device=device))
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def reading(seed, fault, control):
        undo = faulty_run.install(fault)
        try:
            loop = Loop(cfg, mix, seed)
            t0 = time.perf_counter()
            loop.run(0.0, units)
            run_s = time.perf_counter() - t0
        finally:
            undo()
        counts = loop.counts()
        t0 = time.perf_counter()
        numbers, info = loop.check(run_cell.sample_rng(seed),
                                   mix["check_units"])
        ref_s = time.perf_counter() - t0
        emit({"seed": seed, "of": "program" if fault == "none" else fault,
              "numbers": numbers, "gaps": _gap_quartiles(info["gaps"]),
              "pairs": info.get("pairs"),
              "failed": counts["failed"], "checked": info["checked"],
              "run_s": run_s, "reference_s": ref_s})
        if control:
            ctl, cinfo = loop.check(run_cell.sample_rng(seed),
                                    mix["check_units"], rnd=rnd)
            emit({"seed": seed, "of": "control " + cfg["control_dtype"],
                  "numbers": ctl, "gaps": _gap_quartiles(cinfo["gaps"]),
                  "pairs": cinfo.get("pairs"),
                  "checked": cinfo["checked"]})

    for seed in [int(x) for x in args.seeds.split(",") if x]:
        reading(seed, "none", True)
    faults = [f for f in args.faults.split(",") if f]
    for seed in [int(x) for x in args.fault_seeds.split(",") if x]:
        for fault in faults:
            reading(seed, fault, False)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

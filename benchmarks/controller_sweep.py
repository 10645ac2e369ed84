"""Controller regret study: the streaming service vs. hindsight oracles.

Runs the closed-loop scale-ratio controller (`repro.service`) over the
canonical drift scenarios (`repro.workload.windows.drift_scenarios` —
zero-drift control plus intensity/homogeneity ramps and steps) and
records, per scenario and controller, regret against two hindsight
references computed from the same per-tick oracle curves:

  * the per-tick arg-best k (regret >= 0 by construction; the headline
    ``rel_regret_wait`` is total regret over total hindsight-best wait),
  * the offline `plateau_threshold` recommendation applied per window
    (``mean_wait_vs_plateau``, signed — negative = controller beat the
    paper's offline tuning rule).

The A/B at the heart of the study: plateau-aware hysteresis
(`HysteresisController`) vs. a naive every-tick arg-best commit
(`NaiveController`), both realizing their commitment one tick late. The
paper's plateau is the stability argument — under window noise the
arg-best hops between near-tied plateau members, so naive pays the
actuation delay over and over while hysteresis holds still.

``--smoke`` (the CI gate) shrinks the traces and gates the exit code on:

  * regret_wait and regret_useful >= 0 on every scenario (construction
    invariant — a violation means the bookkeeping broke);
  * zero-drift (``steady``) hysteresis rel_regret_wait <= STEADY_BAR;
  * hysteresis switches < naive switches, summed over scenarios;
  * hysteresis total regret <= naive total regret * REGRET_SLACK — the
    switch savings may not be bought with materially worse regret.

``--chaos`` adds the regret-under-faults block: the service re-runs a
scenario subset with a 3-cell `ChaosConfig` axis (harsh / moderate /
calm fault regimes, the harsh cell playing the true environment), the
risk-aware `FaultAwareController` A/B'd against the fault-blind
hysteresis it inherits from. Its gates:

  * fault_aware total lost_work <= fault-blind hysteresis lost_work
    (the λ·lost term must actually buy something);
  * fault_aware wait regret <= hysteresis regret * REGRET_SLACK — the
    lost-work savings may not be bought with materially worse wait;
  * a degrade-mode run under injected `TickFaults` (forced budget
    exhaustion, NaN fault telemetry, a dropped monitor window) completes
    every tick with per-tick health records.

Results land in ``benchmarks/results/BENCH_controller.json`` (or
``--out PATH``). Usage:

    PYTHONPATH=src python benchmarks/controller_sweep.py            # full
    PYTHONPATH=src python benchmarks/controller_sweep.py --smoke --chaos
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import platform
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.des import ChaosConfig
from repro.service import ServiceConfig, TickFaults, run_service
from repro.service.driver import default_controllers
from repro.workload.windows import drift_scenarios

RESULTS = os.path.join(os.path.dirname(__file__), "results")
OUT_PATH = os.path.join(RESULTS, "BENCH_controller.json")

#: zero-drift hysteresis rel regret bar: on a steady trace the held k
#: should track the (noisy) per-window optimum to within plateau noise.
STEADY_BAR = 0.10
#: hysteresis may not trade its switch savings for materially worse
#: regret than naive (total over all scenarios).
REGRET_SLACK = 1.10

# full study: paper-scale node count, ~4000 jobs per scenario, rolling
# 400-job windows advancing 200 jobs per tick -> 19 ticks per scenario.
FULL = dict(n_jobs=4000, nodes=100, n_segments=8,
            window_jobs=400, stride_jobs=200)
# smoke: same shape at CI scale -> 13 ticks per scenario, < ~2 min.
SMOKE = dict(n_jobs=1400, nodes=100, n_segments=7,
             window_jobs=200, stride_jobs=100)

#: --chaos re-runs this scenario subset with the fault axis (the chaos
#: oracle is C=3 times the lanes per tick; the full five-scenario sweep
#: adds nothing the A/B needs).
CHAOS_SCENARIOS = ("steady", "intensity_ramp")
#: ticks the degrade-proof run poisons: forced budget exhaustion, NaN
#: fault telemetry, and a dropped monitor window on distinct ticks.
CHAOS_FAULT_TICKS = dict(exhaust_budget=(1,), nan_telemetry=(2,),
                         drop_telemetry=(3,))
#: study λ: one machine-second of expected lost work priced at 0.1
#: wait-seconds. The per-window lost-work curve is noisy in k, so an
#: aggressive λ makes the cost arg-best chase that noise (extra switches,
#: each paying the one-tick actuation delay in BOTH wait and lost work);
#: a light λ breaks plateau ties toward the low-lost member and at study
#: scale strictly dominates fault-blind hysteresis on lost work at equal
#: or better wait regret.
CHAOS_RISK_LAMBDA = 0.1


def chaos_axis() -> ChaosConfig:
    """The 3-cell fault-regime axis: harsh (25 chip-hour MTBF, deadly
    4x stragglers) / moderate (100) / calm (800, mild stragglers).
    Cell 0 plays the true environment in the study."""
    return ChaosConfig(mtbf_chip_hours=np.array([25.0, 100.0, 800.0]),
                       ckpt_period=300.0, straggler_prob=0.1,
                       straggler_factor=np.array([4.0, 1.5, 1.5]),
                       seed=11)


def _trim_ticks(out: dict) -> None:
    """Keep only the per-tick fields the figures need (the full log is
    bulky). Degraded ticks carry no oracle block — hence the ``in t``
    guard — but keep their tick/window/degraded markers."""
    out["ticks"] = [
        {k: t[k] for k in ("tick", "window", "best_k", "best_wait",
                           "plateau_k", "oracle_ms", "degraded") if k in t} |
        {"controllers": {n: c["realized_k"]
                         for n, c in t["controllers"].items()}}
        for t in out["ticks"]]


def run_study(smoke: bool, scenario_filter=None) -> dict:
    shape = SMOKE if smoke else FULL
    flows = drift_scenarios(n_jobs=shape["n_jobs"], nodes=shape["nodes"],
                            n_segments=shape["n_segments"])
    if scenario_filter:
        missing = set(scenario_filter) - set(flows)
        if missing:
            raise SystemExit(f"unknown scenarios {sorted(missing)}; "
                             f"available: {sorted(flows)}")
        flows = {n: flows[n] for n in scenario_filter}
    config = ServiceConfig(window_jobs=shape["window_jobs"],
                           stride_jobs=shape["stride_jobs"])

    scenarios = {}
    for name, wl in flows.items():
        t0 = time.perf_counter()
        out = run_service(wl, config, default_controllers(config))
        secs = time.perf_counter() - t0
        out["seconds"] = secs
        _trim_ticks(out)
        scenarios[name] = out
        ctl = out["controllers"]
        print(f"[{name}] {out['n_ticks']} ticks in {secs:.1f}s")
        for cname, s in ctl.items():
            print(f"    {cname:10s} switches={s['switches']:2d} "
                  f"rel_regret_wait={s['rel_regret_wait']:.4f} "
                  f"mean_regret_useful={s['mean_regret_useful']:.5f} "
                  f"vs_plateau={s['mean_wait_vs_plateau']:+.2f}s")
    return {"shape": shape, "scenarios": scenarios}


def run_chaos_study(smoke: bool) -> dict:
    """The regret-under-faults block: fault-aware vs. fault-blind on the
    chaos-axis service, plus the degrade-harness proof run."""
    shape = SMOKE if smoke else FULL
    flows = drift_scenarios(n_jobs=shape["n_jobs"], nodes=shape["nodes"],
                            n_segments=shape["n_segments"])
    config = ServiceConfig(window_jobs=shape["window_jobs"],
                           stride_jobs=shape["stride_jobs"],
                           chaos=chaos_axis(), chaos_env_cell=0,
                           risk_lambda=CHAOS_RISK_LAMBDA)

    scenarios = {}
    for name in CHAOS_SCENARIOS:
        t0 = time.perf_counter()
        out = run_service(flows[name], config, default_controllers(config))
        out["seconds"] = time.perf_counter() - t0
        _trim_ticks(out)
        scenarios[name] = out
        print(f"[chaos/{name}] {out['n_ticks']} ticks "
              f"in {out['seconds']:.1f}s")
        for cname, s in out["controllers"].items():
            print(f"    {cname:12s} switches={s['switches']:2d} "
                  f"rel_regret_wait={s['rel_regret_wait']:.4f} "
                  f"lost_work={s['total_lost_work']:.0f} machine-s")

    # degrade-harness proof: the same steady trace with faults injected
    # on three distinct ticks must still complete EVERY tick, with a
    # health record per tick, exactly one of them degraded.
    faults = TickFaults(**{k: frozenset(v)
                           for k, v in CHAOS_FAULT_TICKS.items()})
    proof_cfg = dataclasses.replace(config, on_budget_exhausted="degrade")
    pout = run_service(flows["steady"], proof_cfg,
                       default_controllers(proof_cfg), tick_faults=faults)
    n_expected = scenarios["steady"]["n_ticks"]
    proof = {
        "injected": {k: sorted(v) for k, v in CHAOS_FAULT_TICKS.items()},
        "n_ticks": pout["n_ticks"],
        "n_expected_ticks": n_expected,
        "n_degraded_ticks": pout["n_degraded_ticks"],
        "health": pout["health"],
        "completed_all_ticks": bool(
            pout["n_ticks"] == n_expected
            and len(pout["health"]) == pout["n_ticks"]
            and pout["n_degraded_ticks"]
            == len(CHAOS_FAULT_TICKS["exhaust_budget"])),
    }
    print(f"[chaos/degrade-proof] {pout['n_ticks']}/{n_expected} ticks, "
          f"{pout['n_degraded_ticks']} degraded, "
          f"completed_all_ticks={proof['completed_all_ticks']}")
    return {"config": scenarios["steady"]["config"]["chaos"],
            "scenarios": scenarios, "degrade_proof": proof}


def evaluate_chaos_gates(block: dict) -> dict:
    """The --chaos exit-code gates, also recorded in the JSON."""
    scen = block["scenarios"]
    names = list(next(iter(scen.values()))["controllers"])
    lost = {c: sum(s["controllers"][c]["total_lost_work"]
                   for s in scen.values()) for c in names}
    regret = {c: sum(s["controllers"][c]["total_regret_wait"]
                     for s in scen.values()) for c in names}
    gates = {
        "fault_aware_no_more_lost_work": bool(
            lost["fault_aware"] <= lost["hysteresis"] + 1e-9),
        "total_lost_work": lost,
        "bounded_wait_regret": bool(
            regret["fault_aware"]
            <= regret["hysteresis"] * REGRET_SLACK + 1e-6),
        "total_regret_wait": regret,
        "degrade_completes_all_ticks": bool(
            block["degrade_proof"]["completed_all_ticks"]),
        "regret_slack": REGRET_SLACK,
    }
    gates["ok"] = bool(gates["fault_aware_no_more_lost_work"]
                       and gates["bounded_wait_regret"]
                       and gates["degrade_completes_all_ticks"])
    return gates


def evaluate_gates(study: dict) -> dict:
    """The --smoke exit-code gates, also recorded in the JSON."""
    scen = study["scenarios"]
    nonneg = all(
        s["controllers"][c]["mean_regret_wait"] >= -1e-9
        and s["controllers"][c]["mean_regret_useful"] >= -1e-9
        for s in scen.values() for c in s["controllers"])
    switches = {c: sum(s["controllers"][c]["switches"] for s in scen.values())
                for c in next(iter(scen.values()))["controllers"]}
    regret = {c: sum(s["controllers"][c]["total_regret_wait"]
                     for s in scen.values())
              for c in switches}
    steady_rel = (scen["steady"]["controllers"]["hysteresis"]
                  ["rel_regret_wait"] if "steady" in scen else None)
    gates = {
        "regret_nonnegative": bool(nonneg),
        "hysteresis_fewer_switches": bool(
            switches["hysteresis"] < switches["naive"]),
        "switches": switches,
        "comparable_regret": bool(
            regret["hysteresis"] <= regret["naive"] * REGRET_SLACK + 1e-9),
        "total_regret_wait": regret,
        "steady_rel_regret": steady_rel,
        "steady_rel_regret_ok": (None if steady_rel is None
                                 else bool(steady_rel <= STEADY_BAR)),
        "steady_bar": STEADY_BAR,
        "regret_slack": REGRET_SLACK,
    }
    gates["ok"] = bool(
        gates["regret_nonnegative"] and gates["hysteresis_fewer_switches"]
        and gates["comparable_regret"]
        and gates["steady_rel_regret_ok"] is not False)
    return gates


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Streaming-controller regret study")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-scale traces; exit nonzero if a gate fails")
    ap.add_argument("--chaos", action="store_true",
                    help="add the regret-under-faults block (fault-aware "
                         "vs. fault-blind + the degrade-harness proof)")
    ap.add_argument("--out", default=OUT_PATH,
                    help=f"output JSON path (default {OUT_PATH})")
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated scenario subset (default: all)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    scenario_filter = (args.scenarios.split(",") if args.scenarios else None)
    t0 = time.perf_counter()
    study = run_study(args.smoke, scenario_filter)
    gates = evaluate_gates(study)

    out = {
        "bench": "controller_regret",
        "smoke": bool(args.smoke),
        **study,
        "gates": gates,
        "backend": jax.default_backend(),
        "n_devices": int(jax.device_count()),
        "platform": platform.platform(),
        "unix_time": time.time(),
        "total_seconds": time.perf_counter() - t0,
    }
    chaos_gates = None
    if args.chaos:
        chaos_block = run_chaos_study(args.smoke)
        chaos_gates = evaluate_chaos_gates(chaos_block)
        out["chaos"] = {**chaos_block, "gates": chaos_gates}
        out["total_seconds"] = time.perf_counter() - t0
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"\nwrote {args.out} ({out['total_seconds']:.1f}s)")
    for name, val in gates.items():
        if isinstance(val, bool) or name == "steady_rel_regret_ok":
            print(f"  gate {name}: {val}")
    if chaos_gates is not None:
        for name, val in chaos_gates.items():
            if isinstance(val, bool):
                print(f"  gate chaos.{name}: {val}")
    failed = not gates["ok"] or (chaos_gates is not None
                                 and not chaos_gates["ok"])
    if args.smoke and failed:
        print("SMOKE GATE FAILED")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

#!/usr/bin/env python3
"""One smoke run of the Packet DES study's main path on a TPU chip.

    python3 chip_smoke.py               # one chip: every phase below
    python3 chip_smoke.py --four-chips  # only the lane-sharded fused cohort

Everything runs in this one process (a chip belongs to one process), at
the paper's 5000-job size, with inputs made from seeds and tracked files;
nothing tracked is written. Phases on one chip:

  golden    the golden grid (tests/golden/golden_metrics.json) in float64
            and float32, held to the golden suite's own tolerances;
  paper     `paper_sweep.run_full_grid()`: both cohorts (666 experiments
            each) and the FCFS / EASY-backfill baselines, every lane `ok`;
  chaos     the fault grid on homog0.85 and hetero0.85, every lane `ok`
            and none out of budget;
  cpu-check 8 lanes per cohort of both studies, spanning k, re-run on this
            process's CPU device: equal group counts, avg_wait within the
            golden tolerances;
  pallas    the homogeneous cohort through the compiled Mosaic event-step
            kernel, with and without chaos: integer counters identical to
            the XLA step's, and one chunk's per-job schedule bitwise equal;
  service   `run_service` at controller_sweep's full shape on one drift
            scenario, without and with the 3-cell fault axis: every tick
            healthy, none degraded.

`--four-chips` runs the homogeneous cohort in the padded, lane-sharded
fused layout across four chips, with the XLA step and with the compiled
Pallas step. It checks that each chip holds a quarter of the lanes and
compares every field bitwise with the per-chip program run on one chip.

Each phase prints its wall time, the compile time inside it (set-up) and
its experiments per second; these are one unrepeated smoke run, not a
benchmark. A phase that fails makes the script exit non-zero without the
result line. The last line is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from functools import partial

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT, os.path.join(ROOT, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import test_golden_metrics as golden_suite  # noqa: E402
from benchmarks import controller_sweep, paper_sweep  # noqa: E402
from repro import core, service  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import des, sweep  # noqa: E402
from repro.workload import lublin, windows  # noqa: E402

N_CHECK_LANES = 8


class SmokeFailure(Exception):
    """A phase produced a wrong or incomplete result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _device_or_exit() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default device: {dev}); "
              f"this script runs on the chip only", file=sys.stderr)
        sys.exit(2)
    return dev


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, process-wide."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def run_phase(name, fn, clock, failures) -> object:
    """Run one phase; print its timing line, or record why it failed."""
    c0, t0 = clock.seconds, time.perf_counter()
    try:
        n_exp, result = fn()
    except Exception as e:          # reported below and fails the run
        failures.append(name)
        print(f"[chip_smoke] {name}: FAILED — {type(e).__name__}: {e}",
              flush=True)
        traceback.print_exc()
        return None
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    rate = n_exp / (wall - comp) if wall > comp else float("nan")
    print(f"[chip_smoke] {name}: passed; wall {wall} s, of which compile "
          f"{comp} s (set-up); {n_exp} experiments, {rate} experiments/s "
          f"after compile (one unrepeated smoke run)", flush=True)
    return result


def _worst_rel(got, want, field) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    denom = np.maximum(np.abs(want), golden_suite.ABS_FLOORS[field])
    rel = np.abs(got - want) / denom
    return float(rel.max()) if rel.size else 0.0


def _n_diff(a, b) -> int:
    """Elements that differ, NaN matching NaN."""
    same = a == b
    if a.dtype.kind == "f":
        same |= np.isnan(a) & np.isnan(b)
    return int((~same).sum())


def _max_ulps(a, b) -> int:
    """Largest distance in units in the last place (float32 fields)."""
    if a.dtype != np.float32:
        return 0
    ia, ib = (x.view(np.int32).astype(np.int64) for x in (a, b))
    ia, ib = (np.where(x < 0, np.int64(-2**31) - x, x) for x in (ia, ib))
    return int(np.abs(ia - ib).max()) if a.size else 0


def _rtol(dtype, field) -> float:
    """The golden suite's tolerance: ~ulp in float64 (TestGoldenFloat64),
    the study-derived table in float32 (TestGoldenFloat32)."""
    if np.dtype(dtype) == np.float64:
        return 1e-9
    return golden_suite.float32_rtol()[field]


# ---------------------------------------------------------------- phases

def phase_golden():
    with open(golden_suite.GOLDEN_PATH) as f:
        golden = json.load(f)["grids"]
    n_exp = 0
    for dtype in (np.float64, np.float32):
        got = golden_suite.compute_grids(dtype)
        for name, entry in golden.items():
            for alg in ("packet", "fcfs", "backfill"):
                for f in golden_suite.METRIC_FIELDS:
                    worst = _worst_rel(got[name][alg][f], entry[alg][f], f)
                    check(worst <= _rtol(dtype, f),
                          f"golden {np.dtype(dtype).name}/{name}/{alg}/{f}: "
                          f"max rel deviation {worst} > {_rtol(dtype, f)}")
                check(got[name][alg]["ok"],
                      f"golden {np.dtype(dtype).name}/{name}/{alg}: not ok")
            check(got[name]["packet"]["n_groups"] ==
                  entry["packet"]["n_groups"],
                  f"golden {np.dtype(dtype).name}/{name}: group counts "
                  f"differ from the reference")
            n_exp += np.asarray(entry["packet"]["n_groups"]).size
    return n_exp, None


def _check_study(res, label):
    for name, grids in res["workloads"].items():
        check(np.asarray(grids["ok"]).all(),
              f"{label}/{name}: {int((~np.asarray(grids['ok'])).sum())} "
              f"lane(s) not ok")
        if "budget_exhausted" in grids:
            check(not np.asarray(grids["budget_exhausted"]).any(),
                  f"{label}/{name}: lane(s) exhausted the event budget")
    for name, algs in res["baselines"].items():
        for alg, fields in algs.items():
            for f, v in fields.items():
                check(np.isfinite(np.asarray(v, np.float64)).all(),
                      f"{label}/baseline {name}/{alg}/{f} is not finite")


def phase_paper():
    res = paper_sweep.run_full_grid()
    _check_study(res, "paper")
    n_exp = sum(c["experiments"] for c in res["cohorts"].values())
    return n_exp + 2 * len(res["baselines"]), res


def phase_chaos():
    res = paper_sweep.run_full_grid(chaos=paper_sweep.chaos_grid_config(),
                                    workloads=["homog0.85", "hetero0.85"])
    _check_study(res, "chaos")
    return sum(c["experiments"] for c in res["cohorts"].values()), res


@partial(jax.jit, static_argnums=(3, 4))
def _lane_metrics(pw, k, s, m_nodes, ring, chaos):
    res = des.simulate_packet_scan(pw, k, s, m_nodes, ring=ring, chaos=chaos)
    return core.efficiency_metrics(pw.submit, res, m_nodes, pw.t_last_submit)


def _chaos_cell(cfg, cell: int, lane: int, dtype):
    """The scalar ChaosConfig of one grid lane (`sweep.chaos_lane_grid`)."""
    C = core.chaos_axis_len(cfg)
    pick = lambda x: jnp.asarray(np.broadcast_to(np.asarray(x), (C,))[cell],
                                 dtype)
    return des.ChaosConfig(
        mtbf_chip_hours=pick(cfg.mtbf_chip_hours),
        ckpt_period=pick(cfg.ckpt_period),
        straggler_prob=pick(cfg.straggler_prob),
        straggler_factor=pick(cfg.straggler_factor),
        straggler_deadline=pick(cfg.straggler_deadline),
        lane=jnp.asarray(lane, jnp.int32), seed=cfg.seed,
        max_requeues=cfg.max_requeues)


def phase_cpu_check(studies):
    """Re-run lanes spanning k of each cohort on the host CPU device."""
    cpu = jax.devices("cpu")[0]
    flows = lublin.paper_workloads(seed=0)
    K, S = len(core.PAPER_SCALE_RATIOS), len(core.PAPER_INIT_PROPS)
    i_ks = np.linspace(0, K - 1, N_CHECK_LANES).round().astype(int)
    n_exp = 0
    for label, res, chaos in studies:
        C = 1 if chaos is None else core.chaos_axis_len(chaos)
        for cohort, info in res["cohorts"].items():
            names, dtype = info["workloads"], np.dtype(info["dtype"])
            for i, i_k in enumerate(i_ks):
                name, i_s, c = names[i % len(names)], i % S, i % C
                wl = flows[name]
                with jax.default_device(cpu), \
                        core.precision.dtype_scope(dtype):
                    pw = core.pack_workload(wl, dtype)
                    m = int(wl.params.nodes)
                    s = wl.init_time_for_proportion(core.PAPER_INIT_PROPS[i_s])
                    cell = (None if chaos is None else _chaos_cell(
                        chaos, c, (i_k * S + i_s) * C + c, dtype))
                    ref = jax.device_get(_lane_metrics(
                        pw, jnp.asarray(core.PAPER_SCALE_RATIOS[i_k], dtype),
                        jnp.asarray(s, dtype), m,
                        core.resolve_ring(m, pw.n_jobs), cell))
                at = (i_k, i_s) if chaos is None else (i_k, i_s, c)
                grids = res["workloads"][name]
                chip_groups = int(np.asarray(grids["n_groups"])[at])
                where = f"{label}/{name} lane (k={core.PAPER_SCALE_RATIOS[i_k]}"\
                    f", s_prop={core.PAPER_INIT_PROPS[i_s]}, cell={c})"
                check(bool(ref.ok), f"{where}: CPU lane not ok")
                check(chip_groups == int(ref.n_groups),
                      f"{where}: chip formed {chip_groups} groups, CPU "
                      f"{int(ref.n_groups)}")
                worst = _worst_rel(np.asarray(grids["avg_wait"])[at],
                                   ref.avg_wait, "avg_wait")
                check(worst <= _rtol(dtype, "avg_wait"),
                      f"{where}: avg_wait rel deviation {worst} > "
                      f"{_rtol(dtype, 'avg_wait')}")
                n_exp += 1
    return n_exp, None


INT_FIELDS = ("n_groups", "ok", "failures", "straggler_kills", "requeues",
              "requeued_jobs", "budget_exhausted")


def _homog_cohort(names):
    flows = lublin.paper_workloads(seed=0)
    sel = {n: flows[n] for n in names}
    (cohort,) = core.group_workloads(sel, {n: np.float32 for n in sel})
    return cohort


def _same_schedule(cohort, chaos, label):
    """One 56-lane chunk of the first member, spanning k: per-job start
    times and counters of the Pallas step bitwise equal to the XLA step."""
    wl = cohort.workloads[0]
    K, S = len(core.PAPER_SCALE_RATIOS), len(core.PAPER_INIT_PROPS)
    lanes = np.linspace(0, K * S - 1, 56).round().astype(int)
    ks = jnp.asarray(np.repeat(core.PAPER_SCALE_RATIOS, S)[lanes],
                     jnp.float32)
    ss = jnp.asarray([wl.init_time_for_proportion(p) for p in
                      np.tile(core.PAPER_INIT_PROPS, K)[lanes]], jnp.float32)
    cl = None
    if chaos is not None:
        C = core.chaos_axis_len(chaos)
        cells = [_chaos_cell(chaos, int(i) % C, int(i) * C + int(i) % C,
                             jnp.float32) for i in lanes]
        cl = jax.tree.map(lambda *x: jnp.stack(x), *cells)
    pw = core.pack_workload(wl, np.float32)
    m = int(wl.params.nodes)
    out = {}
    for impl in ("xla", "pallas"):
        run = jax.jit(lambda pw, k, s, ch, impl=impl:
                      des.simulate_packet_scan_lanes(
                          pw, k, s, m, ring=cohort.ring, chaos=ch,
                          step_impl=impl))
        out[impl] = jax.device_get(run(pw, ks, ss, cl))
    for f in out["xla"]._fields:
        a, b = np.asarray(getattr(out["xla"], f)), \
            np.asarray(getattr(out["pallas"], f))
        if f in ("start_t", "run_start_t") or a.dtype.kind in "biu":
            check(np.array_equal(a, b, equal_nan=True),
                  f"{label}: pallas {f} differs from the XLA step's on "
                  f"{int((a != b).sum())} element(s)")


def phase_pallas(paper_res, chaos_res):
    plan = core.sweep_plan("auto", len(core.PAPER_SCALE_RATIOS) *
                           len(core.PAPER_INIT_PROPS), 3, step_impl="pallas")
    check(plan["step_interpret"] is False,
          "the pallas step would run in interpret mode")
    n_exp = 0
    runs = [("pallas", paper_res, None, ("homog0.85", "homog0.90",
                                         "homog0.95"))]
    if chaos_res is not None:
        runs.append(("pallas-chaos", chaos_res,
                     paper_sweep.chaos_grid_config(), ("homog0.85",)))
    for label, ref, chaos, names in runs:
        cohort = _homog_cohort(names)
        grids = core.run_cohort_grid(cohort, chaos=chaos, step_impl="pallas")
        for name in names:
            want = ref["workloads"][name]
            for f in INT_FIELDS:
                if f in want:
                    got = np.asarray(getattr(grids[name], f))
                    check(np.array_equal(got, np.asarray(want[f], got.dtype)),
                          f"{label}/{name}: {f} differs from the XLA step's")
            n_exp += np.asarray(want["n_groups"]).size
        _same_schedule(cohort, chaos, label)
    return n_exp, None


def phase_service():
    shape = controller_sweep.FULL
    flows = windows.drift_scenarios(n_jobs=shape["n_jobs"],
                                    nodes=shape["nodes"],
                                    n_segments=shape["n_segments"])
    wl = flows["intensity_step"]
    n_exp = 0
    for chaos in (None, controller_sweep.chaos_axis()):
        config = service.ServiceConfig(
            window_jobs=shape["window_jobs"],
            stride_jobs=shape["stride_jobs"], chaos=chaos,
            risk_lambda=controller_sweep.CHAOS_RISK_LAMBDA,
            on_budget_exhausted="degrade")
        out = service.run_service(wl, config)
        label = "service" + ("" if chaos is None else "-chaos")
        n_win = (shape["n_jobs"] - shape["window_jobs"]) \
            // shape["stride_jobs"] + 1
        check(out["n_ticks"] == n_win,
              f"{label}: {out['n_ticks']} ticks, expected {n_win}")
        check(out["n_degraded_ticks"] == 0,
              f"{label}: {out['n_degraded_ticks']} degraded tick(s)")
        check(all(h["ok"] for h in out["health"]),
              f"{label}: unhealthy tick(s)")
        n_exp += out["n_ticks"] * len(config.ks) * config.n_chaos_cells
        print(f"[chip_smoke] {label}: {out['n_ticks']} ticks, oracle ms "
              f"per tick {out['oracle']['oracle_ms']}", flush=True)
    return n_exp, None


#: fields that sum over the jobs; a 4x wider dispatch may add them in
#: another order (at most 4 ulp on 4 x TPU v5 lite, 224 lanes)
SUM_OVER_JOBS = ("avg_wait", "avg_run_wait")
MAX_ULPS_ONE_DISPATCH = 8


def phase_four_chips():
    """The fused cohort, lane axis padded and sharded over four chips,
    against the per-chip program on one chip, for both step engines."""
    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, found "
                             f"{len(devices)}")
    names = ("homog0.85", "homog0.90", "homog0.95")
    cohort = _homog_cohort(names)
    K, S = len(core.PAPER_SCALE_RATIOS), len(core.PAPER_INIT_PROPS)
    W, L = cohort.n_workloads, K * S

    # the operands run_cohort_grid(mode="fused") builds and places
    spw = cohort.pack()
    ks = jnp.asarray(core.PAPER_SCALE_RATIOS, jnp.float32)
    s_mat = jnp.stack([jnp.asarray([wl.init_time_for_proportion(p)
                                    for p in core.PAPER_INIT_PROPS],
                                   jnp.float32) for wl in cohort.workloads])
    k_l2 = jnp.broadcast_to(jnp.repeat(ks, S), (W, L))
    s_l2 = jnp.tile(s_mat, (1, K))
    pad = core.lane_padding(L)
    k_l2 = jnp.concatenate([k_l2, jnp.repeat(k_l2[:, -1:], pad, 1)], 1)
    s_l2 = jnp.concatenate([s_l2, jnp.repeat(s_l2[:, -1:], pad, 1)], 1)
    sharding = core.cohort_lane_sharding(L + pad, pad=True)
    check(sharding is not None, "no lane sharding on a four-chip host")
    on0 = partial(jax.device_put, device=devices[0])
    q = (L + pad) // 4
    n_exp, xla_four = 0, None
    for impl in ("xla", "pallas"):
        grids = core.run_cohort_grid(cohort, mode="fused", step_impl=impl)
        # the programs run_cohort_grid runs: per device over four chips,
        # and on one chip the same program over a quarter of the lanes
        # (their metrics; the second output is the lanes' segment counts)
        def prog(sh, impl=impl):
            run = sweep.per_device_lanes(sweep._packet_cohort_lanes, sh,
                                         cohort.m_nodes, cohort.ring, impl)
            return lambda *args: run(*args)[0]
        four = prog(sharding)(spw, jax.device_put(k_l2, sharding),
                              jax.device_put(s_l2, sharding), None)
        shards = four.avg_wait.addressable_shards
        check({s.device for s in shards} == set(devices),
              f"{impl}: lanes sit on "
              f"{sorted(str(s.device) for s in shards)}, not on all four "
              f"chips")
        check(all(s.data.shape == (W, q) for s in shards),
              f"{impl}: shard shapes {[s.data.shape for s in shards]}, "
              f"expected a quarter of {L + pad} lanes each")
        quarters = [prog(None)(on0(spw), on0(k_l2[:, i * q:(i + 1) * q]),
                               on0(s_l2[:, i * q:(i + 1) * q]), None)
                    for i in range(4)]
        check(all(r.avg_wait.devices() == {devices[0]} for r in quarters),
              f"{impl}: the one-chip run did not stay on one chip")
        one = jax.tree.map(lambda *x: np.concatenate(x, axis=1), *quarters)
        four = jax.tree.map(np.asarray, four)
        n_exp += 3 * W * L
        for f in four._fields:
            a, b = getattr(four, f), np.asarray(getattr(one, f))
            print(f"[chip_smoke] four-chips {impl} {f}: {_n_diff(a, b)} "
                  f"element(s) differ from one chip in quarters", flush=True)
            check(_n_diff(a, b) == 0,
                  f"four-chip {impl} {f} differs from one chip")
            for w, name in enumerate(names):
                got = np.asarray(getattr(grids[name], f)).reshape(-1)
                check(np.array_equal(a[w, :L], got, equal_nan=True),
                      f"run_cohort_grid(mode='fused', step_impl={impl!r}) "
                      f"{name}/{f} differs from the sharded program's")
        if impl == "pallas":
            for f in INT_FIELDS:
                check(np.array_equal(getattr(four, f), getattr(xla_four, f)),
                      f"four-chip pallas {f} differs from the XLA step's")
            continue
        xla_four = four
        # the whole padded lane axis in one dispatch on one chip: a layout
        # fault would move lanes; only sums over jobs may round otherwise
        whole = prog(None)(on0(spw), on0(k_l2), on0(s_l2), None)
        n_exp += W * L
        for f in four._fields:
            a, c = getattr(four, f), np.asarray(getattr(whole, f))
            ulps = _max_ulps(a, c)
            print(f"[chip_smoke] four-chips xla {f}: {_n_diff(a, c)} "
                  f"element(s) differ from one chip in one dispatch (max "
                  f"{ulps} ulp)", flush=True)
            if f in SUM_OVER_JOBS:
                check(ulps <= MAX_ULPS_ONE_DISPATCH,
                      f"four-chip {f} is {ulps} ulp from the one-dispatch "
                      f"run on one chip")
            else:
                check(_n_diff(a, c) == 0, f"four-chip {f} differs from the "
                                          f"one-dispatch run on one chip")
    return n_exp, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip lane-sharded fused cohort "
                         "against one chip")
    args = ap.parse_args(argv)
    dev = _device_or_exit()
    print(f"[chip_smoke] device {dev.device_kind} x {jax.device_count()}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    failures: list[str] = []
    if args.four_chips:
        run_phase("four-chips", phase_four_chips, clock, failures)
    else:
        run_phase("golden", phase_golden, clock, failures)
        paper = run_phase("paper", phase_paper, clock, failures)
        chaos = run_phase("chaos", phase_chaos, clock, failures)
        studies = [(lbl, r, c) for lbl, r, c in (
            ("paper", paper, None),
            ("chaos", chaos, paper_sweep.chaos_grid_config()))
            if r is not None]
        run_phase("cpu-check", lambda: phase_cpu_check(studies), clock,
                  failures)
        if paper is not None:
            run_phase("pallas", lambda: phase_pallas(paper, chaos), clock,
                      failures)
        else:
            failures.append("pallas")
        run_phase("service", phase_service, clock, failures)
    if failures:
        print(f"[chip_smoke] failed phases: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""DES microbenchmark: event loops AND sweep dispatch layouts, tracked per PR.

Two sections, both recorded to ``benchmarks/results/BENCH_des.json`` (or
``--out PATH``):

  * ``headline`` / ``scaling_with_n`` — ms/experiment for the simulator
    cores dispatched sequentially:

      - ``reference`` — the seed implementation
        (`simulate_packet_reference`: per-event O(N) masked metric writes,
        fixed 512-slot ring),
      - ``group_log`` — the production while-loop path (`simulate_packet`:
        O(1) log appends + vectorized post-pass, ring = min(M, N)).

  * ``engine_ab`` — the sweep-layout A/B on the same grid through
    `repro.core.sweep`: ``seq`` (cached per-experiment dispatch) vs
    ``chunked`` (sorted fixed-width lanes through the event-budget scan
    engine) vs ``fused`` (all lanes, one program, padded + sharded on
    multi-device backends) vs ``pallas`` (the fused layout on the Pallas
    event-step engine — interpret mode on CPU, recorded with a
    ``pallas_interpret`` flag and exempt from the ratio gate there).
    ``batched_vs_seq_ratio`` is the headline regression number: PR 1's
    vmapped-while fused engine sat at ~16x on a single CPU device; the
    scan engine must stay under ``REGRESSION_BAR`` (2.0), which
    `--smoke` (the CI gate) enforces via the exit code. The ``headline``
    block also carries ``event_step_model`` — the analytic bytes/flops
    per event and the predicted HBM-streaming vs state-resident ceilings
    from `benchmarks.roofline.event_step_roofline`.

  * ``chaos_ab`` — the fault-injection A/B: the same fused grid with
    chaos off (normalized to the exact pre-chaos program) vs a live
    fault sweep (failures + stragglers + requeues, R = N requeue rounds,
    the sized event budget). ``chaos_vs_zero_ratio`` is gated at
    ``REGRESSION_BAR`` in ``--smoke``: fault semantics may not make the
    batched engine more than 2x slower per experiment.

  * ``cohort_ab`` — the workload-axis A/B: a 3-workload study run the
    pre-cohort way (one `run_packet_grid` per workload, Python loop) vs as
    ONE stacked cohort through `run_cohort_grid` (chunked [W, width]
    dispatches and the all-lanes fused program). End-to-end study wall
    clock through the public entry points, so packing/unstacking overhead
    counts on both sides. ``cohort_vs_per_workload_ratio`` (best cohort
    layout / per-workload) is gated at the same ``REGRESSION_BAR`` in
    `--smoke`.

Usage:
    python -m benchmarks.bench_des            # full (5000-job headline)
    python -m benchmarks.bench_des --smoke    # <= ~60 s CI-budget variant
    python -m benchmarks.bench_des --smoke --out smoke.json
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (pack_workload, resolve_ring, simulate_packet,
                        simulate_packet_reference)
from repro.workload.lublin import WorkloadParams, generate_workload

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
BENCH_PATH = os.path.join(RESULTS_DIR, "BENCH_des.json")


REPEATS = 5         # best-of-R to shed scheduler/allocator noise
REGRESSION_BAR = 2.0  # best batched layout must stay within 2x of seq


def _bench_sequential(sim_fn, pw, ks, s, m_nodes, **kw):
    """Best-of ms/experiment for jitted per-k sequential dispatch."""
    f = jax.jit(lambda k: sim_fn(pw, k, s, m_nodes, **kw).makespan)
    f(float(ks[0])).block_until_ready()                   # compile
    best = np.inf
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for k in ks:
            f(float(k)).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / len(ks) * 1e3


def _bench_mode(wl, ks, s_props, mode):
    """Best-of ms/experiment through the sweep layouts in the given mode.

    Inputs are packed once outside the timer (like _bench_sequential), so
    the recorded number is the engine itself, not per-call host repacking.
    Chunked includes its host-side sort/unsort — that is part of the
    layout's real cost. ``mode="pallas"`` runs the fused lane layout with
    the Pallas event-step engine (`step_impl="pallas"`) — on CPU that is
    the interpret-mode fallback, a correctness arm rather than a perf arm
    (the ratio gate skips it; see main()).
    """
    import jax.numpy as jnp
    from repro.core.sweep import (CHUNK_LANES, _packet_one, _run_lane_chunks,
                                  _run_lanes_fused)

    pw = pack_workload(wl)
    m = int(wl.params.nodes)
    ring = resolve_ring(m, pw.n_jobs)
    s_vals = jnp.asarray([wl.init_time_for_proportion(p) for p in s_props],
                         jnp.float32)
    ks_arr = jnp.asarray(ks, jnp.float32)
    k_lanes = jnp.repeat(ks_arr, len(s_props))
    s_lanes = jnp.tile(s_vals, len(ks))

    if mode == "pallas":
        run = lambda: _run_lanes_fused(pw, k_lanes, s_lanes, m, ring,
                                       None, "pallas")
    elif mode == "fused":
        run = lambda: _run_lanes_fused(pw, k_lanes, s_lanes, m, ring)
    elif mode == "chunked":
        run = lambda: _run_lane_chunks(pw, k_lanes, s_lanes, m, ring,
                                       CHUNK_LANES)
    else:
        def run():
            for k in ks_arr:
                for s in s_vals:
                    jax.block_until_ready(_packet_one(pw, k, s, m, ring))
            return None

    out = run()                                           # compile
    if out is not None:
        assert np.asarray(out.ok).all(), mode
    best = np.inf
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best / (len(ks) * len(s_props)) * 1e3


def bench_engine_ab(n_jobs: int, ks, s_props, nodes=100) -> dict:
    """The sweep-layout A/B: seq vs chunked vs fused vs the pallas engine.

    The ``pallas`` arm runs the fused lane layout with the Pallas
    event-step kernel (`step_impl="pallas"`). On CPU the kernel is
    discharged through interpret mode (``pallas_interpret: true``) — a
    correctness/parity arm whose ms/experiment is recorded for tracking
    but exempt from the regression ratio gate; on an accelerator backend
    it compiles for real and the gate applies.
    """
    wl = generate_workload(WorkloadParams(
        n_jobs=n_jobs, nodes=nodes, load=0.9, homogeneous=True, seed=1))
    seq_ms = _bench_mode(wl, ks, s_props, "seq")
    chunked_ms = _bench_mode(wl, ks, s_props, "chunked")
    fused_ms = _bench_mode(wl, ks, s_props, "fused")
    pallas_ms = _bench_mode(wl, ks, s_props, "pallas")
    best_batched = min(chunked_ms, fused_ms)
    return {
        "n_jobs": n_jobs, "nodes": nodes, "n_k": len(ks),
        "n_s": len(s_props), "n_lanes": len(ks) * len(s_props),
        "n_devices": jax.device_count(),
        "seq_ms_per_experiment": seq_ms,
        "chunked_ms_per_experiment": chunked_ms,
        "fused_ms_per_experiment": fused_ms,
        "pallas_ms_per_experiment": pallas_ms,
        "pallas_interpret": jax.default_backend() == "cpu",
        "pallas_vs_fused_ratio": pallas_ms / fused_ms,
        "best_batched_mode": ("chunked" if chunked_ms <= fused_ms
                              else "fused"),
        "batched_vs_seq_ratio": best_batched / seq_ms,
        "regression_bar": REGRESSION_BAR,
    }


def bench_chaos_ab(n_jobs: int, ks, s_props, nodes=100) -> dict:
    """The fault-injection A/B: zero-chaos fused grid vs a live fault sweep.

    Both arms run `run_packet_grid(mode="fused")` end to end — the zero
    arm is the exact pre-chaos program (inert configs normalize away),
    the chaos arm carries the per-lane fault stream, the group-log
    requeue rounds with per-member credit (the searchsorted remnant
    walk; see des.py "requeue"), and the enlarged event budget. Arms are interleaved
    within each repeat round like the cohort A/B: the ratio is the
    quantity under test and runner throughput drifts over these
    seconds-scale studies.
    """
    from repro.core import ChaosConfig, run_packet_grid

    wl = generate_workload(WorkloadParams(
        n_jobs=n_jobs, nodes=nodes, load=0.9, homogeneous=True, seed=1))
    # N/4 requeue rounds bounds the log/budget shapes to the volume this
    # fault intensity actually produces (~N/5 requeues per lane, with
    # headroom), instead of the worst-case default R = N
    chaos = ChaosConfig(mtbf_chip_hours=100.0, ckpt_period=300.0,
                        straggler_prob=0.1, straggler_factor=4.0,
                        straggler_deadline=2.0, seed=7,
                        max_requeues=max(n_jobs // 4, 8))
    n_exp = len(ks) * len(s_props)

    def zero():
        return jax.block_until_ready(
            run_packet_grid(wl, ks, s_props, mode="fused"))

    def with_chaos():
        return jax.block_until_ready(run_packet_grid(
            wl, ks, s_props, mode="fused", chaos=chaos,
            on_budget_exhausted="raise"))

    res = with_chaos()                                # compile + sanity
    assert np.asarray(res.ok).all()
    n_failures = int(np.sum(np.asarray(res.failures)))
    n_kills = int(np.sum(np.asarray(res.straggler_kills)))
    assert n_failures + n_kills > 0, "chaos arm injected nothing"
    # member-credit sanity: the walk must actually requeue members at
    # this fault intensity, and never more than one member set per round
    n_requeues = int(np.sum(np.asarray(res.requeues)))
    n_requeued_jobs = int(np.sum(np.asarray(res.requeued_jobs)))
    assert 0 < n_requeued_jobs <= n_requeues * n_jobs
    zero()
    best = {"zero": np.inf, "chaos": np.inf}
    for _ in range(REPEATS):
        for name, run in (("zero", zero), ("chaos", with_chaos)):
            t0 = time.perf_counter()
            run()
            best[name] = min(best[name], time.perf_counter() - t0)
    return {
        "n_jobs": n_jobs, "nodes": nodes, "n_k": len(ks),
        "n_s": len(s_props), "experiments": n_exp,
        "n_devices": jax.device_count(),
        "failures": n_failures, "straggler_kills": n_kills,
        "requeues": n_requeues,
        "requeued_jobs": n_requeued_jobs,
        "zero_ms_per_experiment": best["zero"] / n_exp * 1e3,
        "chaos_ms_per_experiment": best["chaos"] / n_exp * 1e3,
        "chaos_vs_zero_ratio": best["chaos"] / best["zero"],
        "regression_bar": REGRESSION_BAR,
    }


def bench_cohort_ab(n_jobs: int, ks, s_props, nodes=100) -> dict:
    """The workload-axis A/B: sequential-per-workload vs cohort-batched.

    A 3-workload homogeneous study (loads 0.85/0.90/0.95 — one cohort, the
    same shape the paper's homogeneous half forms) timed end-to-end through
    the public drivers: the pre-cohort layout loops `run_packet_grid` over
    the workloads (each resolving its own single-workload mode, like the
    old paper_sweep driver), the cohort layouts run `run_cohort_grid` on
    the stacked batch. Warmup fills the shared jit caches, so best-of-R
    measures compute + dispatch, not compilation.
    """
    from repro.core import group_workloads, run_cohort_grid, run_packet_grid

    flows = {f"homog{load:.2f}": generate_workload(WorkloadParams(
        n_jobs=n_jobs, nodes=nodes, load=load, homogeneous=True, seed=i + 1))
        for i, load in enumerate((0.85, 0.90, 0.95))}
    cohorts = group_workloads(flows, np.float32)
    assert len(cohorts) == 1, [c.key for c in cohorts]
    cohort = cohorts[0]
    n_exp = len(flows) * len(ks) * len(s_props)

    def per_workload():
        return [jax.block_until_ready(run_packet_grid(wl, ks, s_props))
                for wl in flows.values()]

    def cohort_mode(mode):
        return jax.block_until_ready(
            run_cohort_grid(cohort, ks, s_props, mode=mode))

    # interleave the arms within each repeat round: the ratio is the
    # quantity under test, and shared-runner throughput drifts on a
    # minutes scale, so measuring each arm's best-of back to back (as the
    # engine A/B can afford with its ms-scale passes) would let drift
    # masquerade as a layout difference across these seconds-scale studies
    arms = {"per_workload": per_workload,
            "chunked": lambda: cohort_mode("chunked"),
            "fused": lambda: cohort_mode("fused")}
    best = {}
    for name, run in arms.items():
        run()                                         # compile/warm caches
        best[name] = np.inf
    for _ in range(REPEATS):
        for name, run in arms.items():
            t0 = time.perf_counter()
            run()
            best[name] = min(best[name], time.perf_counter() - t0)
    base_s = best.pop("per_workload")
    times = best
    best_mode = min(times, key=times.get)
    return {
        "n_jobs": n_jobs, "nodes": nodes, "n_workloads": len(flows),
        "n_k": len(ks), "n_s": len(s_props), "experiments": n_exp,
        "n_devices": jax.device_count(),
        "per_workload_study_s": base_s,
        "cohort_chunked_study_s": times["chunked"],
        "cohort_fused_study_s": times["fused"],
        "per_workload_ms_per_experiment": base_s / n_exp * 1e3,
        "cohort_ms_per_experiment": times[best_mode] / n_exp * 1e3,
        "best_cohort_mode": best_mode,
        "cohort_vs_per_workload_ratio": times[best_mode] / base_s,
        "regression_bar": REGRESSION_BAR,
    }


def bench_grid(n_jobs: int, ks, s_props, nodes=100) -> dict:
    wl = generate_workload(WorkloadParams(
        n_jobs=n_jobs, nodes=nodes, load=0.9, homogeneous=True, seed=1))
    pw = pack_workload(wl)
    s = wl.init_time_for_proportion(s_props[0])
    m = wl.params.nodes

    ref_ms = _bench_sequential(simulate_packet_reference, pw, ks, s, m)
    glog_ms = _bench_sequential(simulate_packet, pw, ks, s, m)
    return {
        "n_jobs": n_jobs, "nodes": nodes, "n_k": len(ks),
        "n_s": len(s_props), "ring": resolve_ring(m, n_jobs),
        "n_types": int(pw.n_types),
        "n_devices": jax.device_count(),
        "reference_ms_per_experiment": ref_ms,
        "group_log_ms_per_experiment": glog_ms,
        "speedup_group_log_vs_reference": ref_ms / glog_ms,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes, finishes in ~a minute (the CI "
                         "regression gate)")
    ap.add_argument("--out", default=BENCH_PATH,
                    help="output JSON path (default: results/BENCH_des.json)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.core import PAPER_INIT_PROPS, PAPER_SCALE_RATIOS
    if args.smoke:
        headline_n, scaling_ns = 1200, [300, 600, 1200]
        ks = [0.5, 2.0, 8.0, 50.0]
        s_props = [0.05, 0.5]
        # cohort A/B wants a paper-SHAPED study: enough lanes that the
        # per-workload baseline resolves to its batched layout (as the
        # real driver does) and seconds-long passes that integrate over
        # shared-runner noise, at a job count that fits the CI budget
        cohort_n, cohort_ks, cohort_sp = (
            600, list(PAPER_SCALE_RATIOS), list(PAPER_INIT_PROPS))
    else:
        headline_n, scaling_ns = 5000, [625, 1250, 2500, 5000]
        ks = [0.5, 1.0, 2.0, 4.0, 8.0, 20.0, 50.0, 200.0]
        s_props = [0.05, 0.2, 0.5]
        cohort_n, cohort_ks, cohort_sp = (
            1200, list(PAPER_SCALE_RATIOS), list(PAPER_INIT_PROPS))

    t_start = time.perf_counter()
    print(f"[bench_des] headline grid: {headline_n} jobs, "
          f"{len(ks)} x {len(s_props)} experiments")
    headline = bench_grid(headline_n, ks, s_props)
    print(f"[bench_des]   reference  {headline['reference_ms_per_experiment']:8.1f} ms/exp")
    print(f"[bench_des]   group_log  {headline['group_log_ms_per_experiment']:8.1f} ms/exp "
          f"({headline['speedup_group_log_vs_reference']:.2f}x)")

    # analytic event-step roofline (lazy: roofline.py pulls the model
    # stack at import): the predicted HBM-streaming ceiling for this
    # headline shape on the reference accelerator, and the VMEM-resident
    # ceiling the Pallas event-step kernel targets
    from benchmarks.roofline import event_step_roofline
    headline["event_step_model"] = event_step_roofline(
        headline_n, headline["n_types"], headline["ring"],
        n_lanes=len(ks) * len(s_props))
    esm = headline["event_step_model"]
    print(f"[bench_des]   event-step model ({esm['bound']}-bound): "
          f"{esm['bytes_per_event']} B/event, "
          f"{esm['flops_per_event']} flop/event -> predicted "
          f"{esm['predicted_ms_per_experiment']:.2f} ms/exp HBM-resident, "
          f"{esm['state_resident_ms_per_experiment']:.3f} ms/exp "
          f"state-resident (device ceiling, not this host)")

    print(f"[bench_des] engine A/B: seq vs chunked vs fused "
          f"({len(ks) * len(s_props)} lanes, "
          f"{jax.device_count()} device(s))")
    engine_ab = bench_engine_ab(headline_n, ks, s_props)
    for mode in ("seq", "chunked", "fused", "pallas"):
        print(f"[bench_des]   {mode:8s} "
              f"{engine_ab[f'{mode}_ms_per_experiment']:8.1f} ms/exp")
    print(f"[bench_des]   best batched ({engine_ab['best_batched_mode']}) = "
          f"{engine_ab['batched_vs_seq_ratio']:.2f}x seq "
          f"(bar: {REGRESSION_BAR}x)")
    if engine_ab["pallas_interpret"]:
        print(f"[bench_des]   pallas arm ran interpret-mode (CPU backend): "
              f"parity arm, exempt from the ratio gate")

    print(f"[bench_des] chaos A/B: fused grid, zero-chaos vs fault sweep "
          f"({len(ks) * len(s_props)} experiments)")
    chaos_ab = bench_chaos_ab(headline_n, ks, s_props)
    print(f"[bench_des]   zero-chaos {chaos_ab['zero_ms_per_experiment']:8.1f} ms/exp")
    print(f"[bench_des]   chaos      {chaos_ab['chaos_ms_per_experiment']:8.1f} ms/exp "
          f"({chaos_ab['failures']} failures, "
          f"{chaos_ab['straggler_kills']} kills, "
          f"{chaos_ab['requeues']} requeues, "
          f"{chaos_ab['requeued_jobs']} members requeued)")
    print(f"[bench_des]   chaos = {chaos_ab['chaos_vs_zero_ratio']:.2f}x "
          f"zero-chaos (bar: {REGRESSION_BAR}x)")

    print(f"[bench_des] cohort A/B: 3-workload paper-shaped study, "
          f"per-workload loop vs stacked cohort "
          f"({3 * len(cohort_ks) * len(cohort_sp)} experiments, "
          f"{cohort_n} jobs)")
    cohort_ab = bench_cohort_ab(cohort_n, cohort_ks, cohort_sp)
    print(f"[bench_des]   per-workload  {cohort_ab['per_workload_study_s'] * 1e3:8.0f} ms study "
          f"({cohort_ab['per_workload_ms_per_experiment']:.1f} ms/exp)")
    for mode in ("chunked", "fused"):
        print(f"[bench_des]   cohort {mode:8s} "
              f"{cohort_ab[f'cohort_{mode}_study_s'] * 1e3:5.0f} ms study")
    print(f"[bench_des]   best cohort ({cohort_ab['best_cohort_mode']}) = "
          f"{cohort_ab['cohort_vs_per_workload_ratio']:.2f}x per-workload "
          f"(bar: {REGRESSION_BAR}x)")

    scaling = []
    for n in scaling_ns:
        row = bench_grid(n, ks[:4], s_props[:2])
        scaling.append(row)
        print(f"[bench_des] N={n:5d}: reference "
              f"{row['reference_ms_per_experiment']:.1f} ms, group_log "
              f"{row['group_log_ms_per_experiment']:.1f} ms "
              f"({row['speedup_group_log_vs_reference']:.2f}x)")

    out = {
        "bench": "des_group_log_vs_reference",
        "smoke": bool(args.smoke),
        "backend": jax.default_backend(),
        "n_devices": jax.device_count(),
        "platform": platform.platform(),
        "unix_time": time.time(),
        "total_seconds": None,          # filled below
        "headline": headline,
        "engine_ab": engine_ab,
        "chaos_ab": chaos_ab,
        "cohort_ab": cohort_ab,
        "scaling_with_n": scaling,
    }
    out["total_seconds"] = time.perf_counter() - t_start
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[bench_des] wrote {args.out} "
          f"({out['total_seconds']:.1f}s total)")

    # the pallas arm joins the ratio gate only when it actually compiled
    # (accelerator backend); an interpret-mode CPU run is a parity arm
    # whose wall time says nothing about the kernel
    pallas_ok = (engine_ab["pallas_interpret"] or
                 engine_ab["pallas_vs_fused_ratio"] <= REGRESSION_BAR)
    ok = (headline["speedup_group_log_vs_reference"] >= 2.0 and
          engine_ab["batched_vs_seq_ratio"] <= REGRESSION_BAR and
          pallas_ok and
          chaos_ab["chaos_vs_zero_ratio"] <= REGRESSION_BAR and
          cohort_ab["cohort_vs_per_workload_ratio"] <= REGRESSION_BAR)
    print(f"[bench_des] {'PASS' if ok else 'FAIL'}: group_log >= 2x "
          f"reference AND best batched layout <= {REGRESSION_BAR}x seq "
          f"AND pallas <= {REGRESSION_BAR}x fused (compiled backends only) "
          f"AND chaos <= {REGRESSION_BAR}x zero-chaos "
          f"AND cohort study <= {REGRESSION_BAR}x per-workload")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())

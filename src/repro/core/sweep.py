"""Sweep driver: the paper's experiment grid as batched, shardable XLA programs.

The paper ran 1332 experiments (6 workflows x 37 scale ratios x 6 init
proportions), each "dozens of minutes" in Alea. Here one workload's whole
(k x S) grid is flattened into a lane axis of len(ks) * len(s_props)
experiments (222 per workload for the paper's grid) and driven through one
of three dispatch layouts over the event-budget scan engine
(`repro.core.des.simulate_packet_scan`); a fourth, *cohort*, layer batches
the workload axis on top so the WHOLE study runs as a couple of programs
(`run_cohort_grid`, one per group of same-static workloads):

  * ``"seq"``     — one cached-jit dispatch per experiment (the while-loop
    engine `simulate_packet`). Zero batching overhead; the baseline every
    other mode is measured against. Under `run_cohort_grid` this delegates
    to per-workload sequential dispatch (the pre-cohort driver layout).
  * ``"chunked"`` — lanes sorted by *predicted event count* (monotone
    decreasing in k * s: large scale ratios starve groups of nodes, so the
    queue drains in few big groups) and processed as a few fixed-size
    vmapped dispatches. Lanes of similar event count retire together, so
    the scan's segmented early exit stops each chunk near its own step
    count instead of the grid-wide worst case. This is the fastest layout
    on a single CPU device for paper-sized grids (see
    benchmarks/results/BENCH_des.json). Under `run_cohort_grid` every
    member's sorted chunks are interleaved through one sync-free dispatch
    sequence over device row slices of the stacked operand (workload-FUSED
    [W, width] chunk dispatches were measured and rejected — cache
    pressure; see `_run_cohort_chunks`).
  * ``"fused"``   — ONE program over all lanes. The scalable layout: the
    lane axis is padded up to the next device-count multiple with sentinel
    lanes (copies of the last real lane, sliced off after the gather) and
    placed with a `NamedSharding` over all local devices, so the 222-lane
    paper grid shards on 2/4/8-device backends even though 222 is not a
    power-of-two multiple. Each device runs the one-device program on its
    slice of the lanes (`per_device_lanes`, a `shard_map`). Under
    `run_cohort_grid` the program is [W, L]: the lane axis keeps the
    padded sharding (PartitionSpec(None, "lane")), the stacked workload
    axis is replicated, and one program covers W x lanes experiments (666
    for a 3-flow paper cohort).

The workload axis exists because `simulate_packet_scan` takes the
`PackedWorkload` as an *operand*: `repro.core.cohort.stack_workloads`
stacks same-static workloads along a leading axis and the cohort kernel
vmaps over (pw, k, s) with ``in_axes=(0, 0, 0, None, None)`` — nested over
the per-lane vmap — so no workload table is ever replicated per lane.

The rigid baselines (`repro.core.schedulers`: FCFS and EASY backfill) run
on the same workload axis: `run_cohort_baselines` runs each scheduler as
one program over a cohort's stacked flows x init proportions, and
`run_baselines` is its one-member call.

Why the scan engine: a vmapped `while_loop` (the PR-1 fused engine) carries
the [lanes, N] group log through every lockstep iteration and scatters into
it per event, which lost ~16x to sequential dispatch on one CPU device.
`simulate_packet_scan` instead emits log records as scan outputs, carries
only O(H + ring) state, and runs a branchless masked step over a precomputed
event budget (~3N, with segmented early exit) — batched lanes now cost about
the same per experiment as sequential dispatch, and chunking makes them
cheaper (BENCH_des.json "engine_ab" section tracks the ratio across PRs).

`run_packet_grid(mode="auto")` resolves the layout from lane count and
device count (`resolve_mode`); `sweep_plan` returns the same decision plus
its inputs as a dict so benchmark provenance (e.g. paper_grid.json) records
what actually ran. Compiled entry points are module-level and take the
PackedWorkload as an argument (not a closure), so jit caches are shared
across workloads of equal shape and keyed on dtype (input avals + the x64
trace context): the float64 opt-in (`dtype=jnp.float64`, scoped via
`repro.core.precision`) coexists with float32 sweeps in one session.

Dtype guidance (study: benchmarks/results/BENCH_dtype.json): float32 grids
match float64 to ~7e-3 (waits) / ~2e-6 (utilizations) on homogeneous flows,
but on 5000-job heterogeneous flows 77-83% of cells schedule differently
(near-tie cascades) — `benchmarks/paper_sweep.py` therefore defaults
heterogeneous flows to float64 and records the per-workload decision.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import warnings
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import precision
from repro.core.cohort import group_workloads
from repro.core.des import (SCAN_SEG, STEP_IMPLS, ChaosConfig,
                            PackedWorkload, _check_step_impl,
                            chaos_is_inert, event_budget,
                            pack_workload, resolve_max_requeues,
                            resolve_ring, simulate_packet,
                            simulate_packet_scan, simulate_packet_scan_lanes)
from repro.core.metrics import Metrics, efficiency_metrics
from repro.core.schedulers import RIGID_SCHEDULERS, simulate_rigid
from repro.kernels.packet_step import ops as _step_ops
from repro.workload.lublin import Workload

# the paper's 37 scale-ratio values: 0.1..1 step .1, 1..10 step 1,
# 10..100 step 10, 100..1000 step 100
PAPER_SCALE_RATIOS: tuple[float, ...] = tuple(
    round(v, 1) for v in itertools.chain(
        (i / 10 for i in range(1, 10)),
        range(1, 10),
        range(10, 100, 10),
        range(100, 1001, 100)))
# 5% then 10%..50% step 10% (paper §6)
PAPER_INIT_PROPS: tuple[float, ...] = (0.05, 0.10, 0.20, 0.30, 0.40, 0.50)

assert len(PAPER_SCALE_RATIOS) == 37

SWEEP_MODES = ("auto", "seq", "chunked", "fused", "vmap_k", "vmap_s")
CHUNK_LANES = 64          # chunked-mode dispatch width (measured sweet spot)
CHUNKED_MIN_LANES = 32    # below this, per-dispatch batching can't amortize
# Measured same-schedule float32 deviation ceiling for avg_wait over the
# full paper grid (benchmarks/results/BENCH_dtype.json
# `suggested_float32_rtol`, 10x the worst rounding-only deviation). Used as
# the default absolute-slack scale in `plateau_threshold`, so the plateau
# call is exactly as tolerant as float32 arithmetic is imprecise.
FLOAT32_AVG_WAIT_RTOL = 0.031


def _one_experiment(pw, k, s, m_nodes, ring, chaos=None):
    res = simulate_packet(pw, k, s, m_nodes, ring=ring, chaos=chaos)
    return efficiency_metrics(pw.submit, res, m_nodes, pw.t_last_submit)


def _one_experiment_scan(pw, k, s, m_nodes, ring, chaos=None):
    res = simulate_packet_scan(pw, k, s, m_nodes, ring=ring, chaos=chaos)
    return efficiency_metrics(pw.submit, res, m_nodes, pw.t_last_submit)


def _lane_experiment(pw, k, s, m_nodes, ring, chaos=None):
    """`_one_experiment_scan` and the lane's scan segment count."""
    res, segs = simulate_packet_scan(pw, k, s, m_nodes, ring=ring,
                                     chaos=chaos, with_segments=True)
    return (efficiency_metrics(pw.submit, res, m_nodes, pw.t_last_submit),
            segs)


@partial(jax.jit, static_argnames=("m_nodes", "ring", "step_impl"))
def _packet_one(pw, k, s, m_nodes, ring, chaos=None, step_impl="xla"):
    """Single experiment (the per-dispatch path of mode='seq').

    Without chaos this is the while-loop engine, bitwise-identical to every
    pre-chaos release. Chaos runs dispatch the scan engine instead: the
    sweep contract is that seq/chunked/fused agree *bitwise* on a seeded
    fault sweep, and only a shared engine can promise that — LLVM
    contracts mul+add into FMA at codegen, below HLO-level
    `optimization_barrier`s, so the two engines' differently-shaped loop
    bodies can legally round a metric accumulate differently in either
    dtype (observed: 1-2 ulp in qlen_int). Cross-engine chaos agreement
    is still enforced, engine-level, by tests/test_chaos.py: schedules
    and counters exact, float accumulates allclose (tight in float64).

    ``step_impl="pallas"`` always routes through the scan engine (the
    kernel is a scan-step implementation), chaos or not — so a pallas
    "seq" sweep A/Bs engine-level against the batched layouts, while the
    XLA default keeps the historical while-engine fast path.
    """
    if step_impl == "pallas":
        res = simulate_packet_scan(pw, k, s, m_nodes, ring=ring,
                                   chaos=chaos, step_impl="pallas")
        return efficiency_metrics(pw.submit, res, m_nodes, pw.t_last_submit)
    if chaos is None:
        return _one_experiment(pw, k, s, m_nodes, ring)
    return _one_experiment_scan(pw, k, s, m_nodes, ring, chaos)


@partial(jax.jit, static_argnames=("m_nodes", "ring", "step_impl"))
def _packet_lanes(pw, k_lanes, s_lanes, m_nodes, ring, chaos=None,
                  step_impl="xla"):
    """Batched lanes through the event-budget scan engine (chunked/fused).

    Returns ``(Metrics, segments)``: [L] metrics and each lane's scan
    segment count (`simulate_packet_scan(..., with_segments=True)`).

    `chaos` is either None (the pre-chaos trace) or a ChaosConfig whose
    leaves are [L]-aligned with the lane axis (ChaosConfig's static aux —
    seed, max_requeues — keys the jit cache via the treedef).

    ``step_impl="pallas"`` runs the same lanes through the fused
    event-step kernel (`des.simulate_packet_scan_lanes`) instead of the
    vmapped XLA step — one kernel invocation advances the whole dispatch
    one event, with bitwise-identical schedules and counters."""
    if step_impl == "pallas":
        res, segs = simulate_packet_scan_lanes(
            pw, k_lanes, s_lanes, m_nodes, ring=ring, chaos=chaos,
            step_impl="pallas", with_segments=True)
        return jax.vmap(
            lambda r: efficiency_metrics(pw.submit, r, m_nodes,
                                         pw.t_last_submit))(res), segs
    if chaos is None:
        return jax.vmap(_lane_experiment,
                        in_axes=(None, 0, 0, None, None))(
            pw, k_lanes, s_lanes, m_nodes, ring)
    return jax.vmap(_lane_experiment,
                    in_axes=(None, 0, 0, None, None, 0))(
        pw, k_lanes, s_lanes, m_nodes, ring, chaos)


@partial(jax.jit, static_argnames=("m_nodes", "ring"))
def _packet_k_column(pw, ks_arr, s, m_nodes, ring):
    """One init-proportion column batched over the scale-ratio axis."""
    return jax.vmap(_one_experiment_scan, in_axes=(None, 0, None, None, None))(
        pw, ks_arr, s, m_nodes, ring)


@partial(jax.jit, static_argnames=("m_nodes", "ring"))
def _packet_s_row(pw, k, s_vals, m_nodes, ring):
    """One scale-ratio row batched over the init-proportion axis."""
    return jax.vmap(_one_experiment_scan, in_axes=(None, None, 0, None, None))(
        pw, k, s_vals, m_nodes, ring)


#: the ChaosConfig fields that may carry a chaos lane axis
CHAOS_AXIS_FIELDS = ("mtbf_chip_hours", "ckpt_period", "straggler_prob",
                     "straggler_factor", "straggler_deadline")


def chaos_axis_len(chaos: ChaosConfig | None) -> int:
    """Length C of the chaos lane axis: 1 for a scalar ChaosConfig, else the
    shared leading dim of its array-valued fault parameters.

    Scalar/array mixes are legal (scalars broadcast over the axis), but
    every array-valued parameter must share ONE length and be 1-D; both
    violations raise here, naming the offending fields, instead of
    surfacing as a broadcast shape error deep inside `chaos_lane_grid`."""
    if chaos is None:
        return 1
    sizes: dict[str, int] = {}
    for name in CHAOS_AXIS_FIELDS:
        x = getattr(chaos, name)
        nd = np.ndim(x)
        if nd > 1:
            raise ValueError(
                f"ChaosConfig.{name} must be a scalar or a 1-D chaos axis, "
                f"got shape {np.shape(x)}")
        if nd:
            sizes[name] = int(np.shape(x)[0])
    arrays = {n: s for n, s in sizes.items() if s != 1}
    uniq = sorted(set(arrays.values()))
    if len(uniq) > 1:
        detail = ", ".join(f"{n}[{s}]" for n, s in sorted(arrays.items()))
        raise ValueError(
            f"ChaosConfig fault parameters have mismatched chaos-axis "
            f"lengths: {detail}; array-valued parameters must share one "
            f"leading length (scalars broadcast)")
    return uniq[0] if uniq else 1


def chaos_lane_grid(chaos: ChaosConfig, n_grid: int, dtype) -> tuple:
    """Broadcast a ChaosConfig over the flat (k, s) lane axis.

    Returns ``(chaos_lanes, C)``: every fault parameter becomes a
    [n_grid * C] array (grid-major, chaos-minor — cell (i_k, i_s) owns the
    C consecutive lanes starting at (i_k * S + i_s) * C) and `lane` is
    overwritten with the flat experiment index. The lane id is assigned in
    GRID order, before any chunk sorting or fused padding, so the per-lane
    uniform stream is identical across every dispatch layout.
    """
    C = chaos_axis_len(chaos)

    def tile(x):
        arr = jnp.broadcast_to(jnp.asarray(x, dtype), (C,))
        return jnp.tile(arr, n_grid)

    lanes = dataclasses.replace(
        chaos,
        mtbf_chip_hours=tile(chaos.mtbf_chip_hours),
        ckpt_period=tile(chaos.ckpt_period),
        straggler_prob=tile(chaos.straggler_prob),
        straggler_factor=tile(chaos.straggler_factor),
        straggler_deadline=tile(chaos.straggler_deadline),
        lane=jnp.arange(n_grid * C, dtype=jnp.int32))
    return lanes, C


def _chaos_cell(chaos_lanes: ChaosConfig, i: int) -> ChaosConfig:
    """Scalar ChaosConfig for one flat lane (the mode='seq' dispatch)."""
    return jax.tree.map(lambda x: x[i], chaos_lanes)


_BUDGET_CELLS_SHOWN = 8    # exhausted cells named per message


def _format_budget_cells(bad: np.ndarray, ks=None, s_props=None,
                         axis_names=None) -> str:
    """Name the exhausted grid cells: indices along the metric axes
    ((i_k, i_s[, i_chaos]) for a reshaped grid, a flat lane index
    otherwise) plus the actual k / s_prop values when the caller's axes
    are known. `axis_names` overrides the default axis labels (the
    window oracle's second axis is the chaos cell, not an init
    proportion). Truncated after `_BUDGET_CELLS_SHOWN` entries."""
    if bad.ndim == 0:
        return "the single experiment"
    idx = np.argwhere(bad)
    if axis_names is not None:
        names = tuple(axis_names)[:bad.ndim]
    else:
        names = (("i_k", "i_s", "i_chaos")[:bad.ndim] if bad.ndim <= 3
                 else tuple(f"i{d}" for d in range(bad.ndim)))
    shown = []
    for cell in idx[:_BUDGET_CELLS_SHOWN]:
        cell = tuple(int(v) for v in cell)
        parts = ([f"lane={cell[0]}"] if bad.ndim == 1 else
                 [f"{n}={v}" for n, v in zip(names, cell)])
        if bad.ndim >= 2:
            if ks is not None and cell[0] < len(ks):
                parts.append(f"k={float(ks[cell[0]]):g}")
            if s_props is not None and cell[1] < len(s_props):
                parts.append(f"s_prop={float(s_props[cell[1]]):g}")
        shown.append("(" + ", ".join(parts) + ")")
    more = len(idx) - len(shown)
    return "; ".join(shown) + (f"; ... {more} more" if more > 0 else "")


def _enforce_budget(metrics, policy: str, label: str,
                    ks=None, s_props=None, axis_names=None):
    """raise / warn / ignore when any lane hit its event budget.

    A truncated lane means its schedule (and every metric) stops early —
    silently mixing those cells into a grid is how the pre-PR-6 driver hid
    starved runs, so the default is to raise. The message names the
    exhausted cells (grid indices and, when the caller passes its axes,
    the (k, s_prop) values — the chaos index identifies the fault cell via
    the sweep plan's `chaos` block), so a truncated 1332-cell run is
    diagnosable without re-running it.
    """
    if policy not in ("raise", "warn", "ignore"):
        raise ValueError(f"on_budget_exhausted must be 'raise', 'warn' or "
                         f"'ignore', got {policy!r}")
    if policy == "ignore":
        return
    bad = np.asarray(metrics.budget_exhausted)
    n_bad = int(bad.sum())
    if n_bad:
        msg = (f"{label}: {n_bad} lane(s) exhausted the event budget at "
               f"[{_format_budget_cells(bad, ks, s_props, axis_names)}] — "
               f"schedules "
               f"are truncated; raise max_requeues/budget or pass "
               f"on_budget_exhausted='ignore' to keep them")
        if policy == "raise":
            raise RuntimeError(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def predicted_lane_events(k_lanes, s_lanes) -> np.ndarray:
    """Relative event-count predictor used to sort lanes into chunks.

    The scan engine's step count is N + 2G where G is the number of groups
    formed. G is monotone *decreasing* in both the scale ratio k and the
    init time s: large k means few nodes per group (m = ceil(W / (k s))),
    long group durations and a queue that drains in few big groups, while
    small k * s forms a near-singleton group per job (G -> N). The product
    k * s is therefore a monotone proxy; lanes are sorted by it so chunk
    neighbours retire at similar step counts. Only the ORDER matters —
    budgets stay at the safe `event_budget` bound and early exit does the
    rest — so the proxy needs no calibration.
    """
    score = np.asarray(k_lanes, np.float64) * np.asarray(s_lanes, np.float64)
    return -score        # descending events == ascending k * s


def lane_order(k_lanes, s_lanes) -> np.ndarray:
    """Stable lane permutation: predicted-longest lanes first."""
    return np.argsort(-predicted_lane_events(k_lanes, s_lanes), kind="stable")


def lane_padding(n_lanes: int, n_devices: int | None = None) -> int:
    """Sentinel lanes needed to round n_lanes up to a device multiple."""
    if n_devices is None:
        n_devices = jax.device_count()
    return (-n_lanes) % max(1, n_devices)


def lane_sharding(n_lanes: int, pad: bool = False):
    """NamedSharding splitting the experiment lane axis across all devices.

    Returns None on a single device or (by default) when the lane count
    does not divide the device count — callers following the historical
    ``if sharding is not None: device_put(...)`` pattern keep the
    replicated fallback. ``pad=True`` declares the caller pads the lane
    axis with `lane_padding` sentinel lanes before placement (as
    `run_packet_grid(mode="fused")` does), so any lane count shards — the
    paper's 222-lane grid included — on 2/4/8-device backends.
    """
    devices = jax.devices()
    if len(devices) <= 1:
        return None
    if not pad and n_lanes % len(devices) != 0:
        return None
    mesh = jax.sharding.Mesh(np.asarray(devices), ("lane",))
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("lane"))


@functools.lru_cache(maxsize=None)
def per_device_lanes(program, sharding, m_nodes: int, ring: int,
                     step_impl: str):
    """``program(pw, k, s, m_nodes, ring, chaos, step_impl=...)`` as a
    function of ``(pw, k, s, chaos)`` that runs per device.

    With a lane `sharding` (`lane_sharding` / `cohort_lane_sharding`) it
    is one jitted `shard_map`: the workload operand is replicated, `k`/`s`
    and the outputs follow the sharding, the [L] chaos leaves follow the
    lane axis, and each device runs `program` on its own slice of lanes.
    Lanes are independent, so that slice is exactly the one-device
    program at 1/n_devices of the width. Mosaic does not partition the
    compiled pallas step by itself, so this explicit form is what lets
    the fused layout shard it; the XLA step takes the same form. With
    ``sharding=None`` it is `program` on one device.
    """
    def body(pw, k, s, chaos):
        return program(pw, k, s, m_nodes, ring, chaos, step_impl=step_impl)
    if sharding is None:
        return body
    lane = sharding.spec
    return jax.jit(jax.shard_map(
        body, mesh=sharding.mesh, in_specs=(P(), lane, lane, P("lane")),
        out_specs=lane, check_vma=False))


def resolve_mode(mode: str, n_lanes: int, n_workloads: int = 1,
                 step_impl: str = "xla") -> str:
    """Resolve mode='auto' to the concrete dispatch layout; validate others.

    Measured heuristics (benchmarks/results/BENCH_des.json, single CPU
    device vs sharded backends), applied to the TOTAL experiment count
    ``n_lanes * n_workloads`` (`n_lanes` stays the per-workload lane count;
    ``n_workloads > 1`` is the cohort path of `run_cohort_grid`):

      * more than one device -> "fused": the padded lane axis shards, and
        per-device lane counts shrink with the device count.
      * one device, >= CHUNKED_MIN_LANES total experiments -> "chunked":
        sorted chunks through the scan engine beat sequential dispatch on
        paper-sized grids and stay within ~1.2x on small ones.
      * one device, small study -> "seq": nothing to amortize.

    Any explicit mode must be one of SWEEP_MODES; unknown strings raise
    instead of silently falling through to a default layout.

    `step_impl` (validated here so every driver rejects typos up front) is
    ORTHOGONAL to the layout: seq/chunked/fused describe how lanes are
    grouped into dispatches, the step implementation ("xla" | "pallas")
    describes what executes one event inside each dispatch. The legacy
    vmap_k/vmap_s layouts predate the engine knob and stay XLA-only.
    """
    _check_step_impl(step_impl)
    if mode not in SWEEP_MODES:
        raise ValueError(
            f"unknown sweep mode {mode!r}; available: {SWEEP_MODES}")
    if step_impl == "pallas" and mode in ("vmap_k", "vmap_s"):
        raise ValueError(
            f"mode {mode!r} is a legacy XLA-only layout; the pallas step "
            f"runs under 'seq', 'chunked' or 'fused'")
    if mode != "auto":
        return mode
    total = n_lanes * max(1, int(n_workloads))
    if jax.device_count() > 1 and total >= jax.device_count():
        return "fused"
    return "chunked" if total >= CHUNKED_MIN_LANES else "seq"


def sweep_plan(mode: str, n_lanes: int, n_workloads: int = 1,
               chaos: ChaosConfig | None = None,
               step_impl: str = "xla") -> dict:
    """The resolve_mode decision plus its inputs, for benchmark provenance.

    `benchmarks/paper_sweep.py` persists this next to the metrics so a
    paper_grid.json records not just WHAT ran but WHY that layout was
    picked (lane count, workload/cohort layout, device count, padding,
    chunk width). ``n_workloads > 1`` describes a cohort study: the plan
    then reports the stacked [W, lanes] layout `run_cohort_grid` executes.
    A `chaos` config multiplies the lane axis by its fault-parameter length
    C and records the fault grid (seed, requeue bound, parameter values)
    so a chaos sweep's provenance pins the exact draws. `step_impl`
    records which event-step engine runs inside each dispatch
    ("xla" | "pallas"); `step_interpret` flags a pallas run discharged
    through interpret mode (CPU backend) — a parity run, not a perf run,
    which is why bench_des skips its regression ratio gate.
    """
    if chaos_is_inert(chaos):
        chaos = None        # mirror the run_* drivers' normalization
    C = chaos_axis_len(chaos)
    n_lanes = int(n_lanes) * C
    resolved = resolve_mode(mode, n_lanes, n_workloads, step_impl)
    n_workloads = max(1, int(n_workloads))
    plan = {
        "requested_mode": mode,
        "mode": resolved,
        "step_impl": step_impl,
        "step_interpret": bool(step_impl == "pallas"
                               and _step_ops.interpret_mode()),
        "n_lanes": n_lanes,
        "n_workloads": n_workloads,
        "total_experiments": n_lanes * n_workloads,
        "n_devices": int(jax.device_count()),
        "lane_pad": int(lane_padding(n_lanes)) if resolved == "fused" else 0,
        "chunk_lanes": CHUNK_LANES if resolved == "chunked" else None,
        "chunked_min_lanes": CHUNKED_MIN_LANES,
    }
    if chaos is not None:
        plan["chaos"] = {
            "axis_len": C,
            # requeue-credit semantics marker: absent in pre-PR-7 plans
            # (aggregate pool), "per-member" since the member-span walk
            "requeue_credit": "per-member",
            "seed": int(chaos.seed),
            "max_requeues": (None if chaos.max_requeues is None
                             else int(chaos.max_requeues)),
            "mtbf_chip_hours": np.asarray(chaos.mtbf_chip_hours,
                                          np.float64).tolist(),
            "ckpt_period": np.asarray(chaos.ckpt_period,
                                      np.float64).tolist(),
            "straggler_prob": np.asarray(chaos.straggler_prob,
                                         np.float64).tolist(),
            "straggler_factor": np.asarray(chaos.straggler_factor,
                                           np.float64).tolist(),
            "straggler_deadline": np.asarray(chaos.straggler_deadline,
                                             np.float64).tolist(),
        }
    return plan


def _tally(sp, n_jobs: int, n_groups, loops) -> None:
    """Count a dispatch on its span, from outputs already on the host.

    ``lane_events``: N + 2 * n_groups summed over its real lanes
    (`n_groups`), the steps that move a lane's schedule on.
    ``lane_steps_run``: the steps it executed, masked or not: for each
    while loop it ran (`loops`, the segment counts of that loop's lanes,
    padded lanes included) its lanes x its longest lane's segments x
    `SCAN_SEG`. One loop per dispatch and chip; one per member as well
    where the pallas step runs a cohort."""
    n_groups = np.asarray(n_groups)
    sp.count("lane_events",
             n_jobs * int(n_groups.size) + 2 * int(n_groups.sum()))
    sp.count("lane_steps_run", SCAN_SEG * sum(
        int(np.size(seg)) * int(np.max(seg)) for seg in loops))


def _run_lane_chunks(pw, k_lanes, s_lanes, m_nodes, ring, chunk: int,
                     chaos=None, step_impl="xla"):
    """Sorted equal-width chunks through the scan engine, then unsort.

    The requested `chunk` width only sets the number of dispatches
    (ceil(L / chunk)); the actual width is balanced to ceil(L / n_chunks)
    so a grid slightly over a chunk boundary doesn't pay a nearly-empty
    padded dispatch (222 lanes at width 64 -> 4 dispatches of 56, not
    3 x 64 + 30). Every chunk is padded to exactly that width (repeating
    its last lane) so all dispatches share one compiled program; the
    inverse permutation restores grid order before reshaping.

    `chaos` (when given) carries [L]-aligned fault-parameter leaves and is
    gathered by the SAME permutation as k/s — each lane keeps its grid-order
    lane id, so the per-lane uniform stream is sort-invariant.
    """
    L = int(k_lanes.shape[0])
    n_chunks = max(1, -(-L // max(1, chunk)))
    width = -(-L // n_chunks)
    order = lane_order(np.asarray(k_lanes), np.asarray(s_lanes))
    chunks = []
    for i, c in enumerate(range(0, L, width)):
        idx = order[c:c + width]
        pad = width - len(idx)
        if pad:
            idx = np.concatenate([idx, np.repeat(idx[-1:], pad)])
        chaos_c = (None if chaos is None
                   else jax.tree.map(lambda x: jnp.asarray(x)[idx], chaos))
        with obs.span("repro.sweep.dispatch", chunk=i, lanes=width - pad,
                      width=width) as sp:
            out, segs = _packet_lanes(pw, k_lanes[idx], s_lanes[idx],
                                      m_nodes, ring, chaos_c,
                                      step_impl=step_impl)
            # copied with the outputs, not in a host round trip of its own
            segs.copy_to_host_async()
            obs.stamp_when_ready("repro.sweep.device", segs)
        out = jax.tree.map(np.asarray, out)
        _tally(sp, pw.n_jobs, out.n_groups[:width - pad], [np.asarray(segs)])
        chunks.append(jax.tree.map(lambda x: x[:width - pad] if pad else x,
                                   out))
    gathered = jax.tree.map(lambda *x: np.concatenate(x, axis=0), *chunks)
    inv = np.empty_like(order)
    inv[order] = np.arange(L)
    return jax.tree.map(lambda x: x[inv], gathered)


def _run_lanes_fused(pw, k_lanes, s_lanes, m_nodes, ring, chaos=None,
                     step_impl="xla"):
    """All lanes in one dispatch, lane axis padded + sharded when possible."""
    L = int(k_lanes.shape[0])
    pad = lane_padding(L)
    if pad:
        k_lanes = jnp.concatenate([k_lanes, jnp.repeat(k_lanes[-1:], pad)])
        s_lanes = jnp.concatenate([s_lanes, jnp.repeat(s_lanes[-1:], pad)])
        if chaos is not None:
            # sentinel lanes replay the last real lane (same lane id ->
            # same stream); their rows are sliced off below
            chaos = jax.tree.map(
                lambda x: jnp.concatenate([x, jnp.repeat(x[-1:], pad)]),
                chaos)
    sharding = lane_sharding(L + pad, pad=True)
    if sharding is not None:
        k_lanes = jax.device_put(k_lanes, sharding)
        s_lanes = jax.device_put(s_lanes, sharding)
        if chaos is not None:
            chaos = jax.device_put(chaos, sharding)
    with obs.span("repro.sweep.dispatch", chunk=0, lanes=L,
                  width=L + pad) as sp:
        out, segs = per_device_lanes(_packet_lanes, sharding, m_nodes, ring,
                                     step_impl)(pw, k_lanes, s_lanes, chaos)
        segs.copy_to_host_async()
        obs.stamp_when_ready("repro.sweep.device", segs)
    out, segs = jax.tree.map(np.asarray, (out, segs))
    chips = 1 if sharding is None else len(sharding.device_set)
    _tally(sp, pw.n_jobs, out.n_groups[:L], np.split(segs, chips))
    return jax.tree.map(lambda x: x[:L], out)


# --------------------------------------------------------------------------
# Cohort layer: the workload axis (repro.core.cohort).
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("m_nodes", "ring", "step_impl"))
def _packet_cohort_lanes(spw, k_lanes, s_lanes, m_nodes, ring, chaos=None,
                         step_impl="xla"):
    """[W]-stacked workloads x [W, L] lanes: one program, W * L experiments.

    Returns ``(Metrics, segments)``, both [W, L] (as `_packet_lanes`).

    The outer vmap batches the PackedWorkload operand itself
    (in_axes=(0, 0, 0, None, None)); the inner vmap is the existing lane
    axis. Static aux (n_types, n_jobs) is shared by construction
    (`repro.core.cohort.stack_workloads` validates), so the jit cache keys
    on one shape for the whole cohort.

    `chaos` leaves are [L] and SHARED across the workload axis (common
    random numbers: every member sees the same per-lane fault stream, so
    cross-workload comparisons at a grid cell difference out the draws).

    ``step_impl="pallas"`` unrolls the (small, static) workload axis into
    one fused-kernel lane dispatch per member inside the same program —
    the kernel batches lanes, not workload tables, so each member keeps
    its own prefix tables as kernel operands.
    """
    if step_impl == "pallas":
        rows = []
        for w in range(int(k_lanes.shape[0])):
            pw_w = jax.tree.map(lambda x, w=w: x[w], spw)
            res, segs = simulate_packet_scan_lanes(
                pw_w, k_lanes[w], s_lanes[w], m_nodes, ring=ring,
                chaos=chaos, step_impl="pallas", with_segments=True)
            rows.append((jax.vmap(
                lambda r, p=pw_w: efficiency_metrics(
                    p.submit, r, m_nodes, p.t_last_submit))(res), segs))
        return jax.tree.map(lambda *x: jnp.stack(x), *rows)
    if chaos is None:
        lanes = jax.vmap(_lane_experiment,
                         in_axes=(None, 0, 0, None, None))
        return jax.vmap(lanes, in_axes=(0, 0, 0, None, None))(
            spw, k_lanes, s_lanes, m_nodes, ring)
    lanes = jax.vmap(_lane_experiment,
                     in_axes=(None, 0, 0, None, None, 0))
    return jax.vmap(lanes, in_axes=(0, 0, 0, None, None, None))(
        spw, k_lanes, s_lanes, m_nodes, ring, chaos)


# NOTE: there is deliberately no while-engine cohort kernel. Vmapping
# `simulate_packet` over the workload axis (one (k, s) cell at a time,
# in_axes=(0, None, 0, None, None)) is bitwise-correct but measured ~4x
# SLOWER than per-workload sequential dispatch on one CPU device even at
# W = 3: the event loop's gather/scatter body vectorizes as badly over
# workloads as it did over lanes (the PR-1 fused-engine regression), and
# lockstep iteration pays the slowest member's event count in every cell.
# Small cohort studies therefore resolve to "seq" = per-workload delegation.


def cohort_lane_sharding(n_lanes: int, pad: bool = False):
    """NamedSharding for a [W, lanes] cohort batch: lane axis split over all
    local devices, workload axis replicated.

    Same contract as `lane_sharding` (None on one device; ``pad=True``
    declares the caller padded the lane axis with `lane_padding` sentinel
    lanes), but with a leading unsharded workload dimension — every device
    computes all W workloads over its slice of lanes, so cohort and
    single-workload fused dispatches balance identically.
    """
    devices = jax.devices()
    if len(devices) <= 1:
        return None
    if not pad and n_lanes % len(devices) != 0:
        return None
    mesh = jax.sharding.Mesh(np.asarray(devices), ("lane",))
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, "lane"))


def _run_cohort_chunks(spw, k_l2, s_l2, m_nodes, ring, chunk: int,
                       chaos=None, step_impl="xla"):
    """Sorted chunks of every member's lanes, interleaved without syncs.

    The measured single-device cohort layout. Workload-fusing each chunk
    into a [W, width] block (`_packet_cohort_lanes` on narrow slices) was
    tried first and LOSES on CPU for paper-sized jobs counts: every scan
    step then walks W workloads' per-type tables (W x ~N floats), which
    falls out of cache — 1.4x slower than per-workload dispatch at
    N = 2500 on a 2-core CPU, the same locality cliff that made PR 3
    chunk the lane axis. Instead each member's lanes run through the
    single-workload chunk kernel (`_packet_lanes`, device-side row slices
    of the stacked operand, so the jit cache is shared with
    `run_packet_grid`), and the whole W x n_chunks dispatch sequence is
    issued WITHOUT host syncs: outputs stay on device until the caller's
    final conversion, so chunk c+1 (and workload w+1) enqueue while c
    still computes, where the sequential driver blocks per chunk.

    Lane order is computed once from the first member's (k, s) row and
    shared: the k grid is identical across members and init times differ
    only by a positive per-workload scalar (s_w = S/(1-S) * mean(e_w)), so
    the k * s event-count proxy sorts every row identically.

    Returns ``(Metrics, segments, dispatches)``: [W, L] device arrays in
    grid order, and per dispatch its span with the host indices of its
    real lanes and of its loop's lanes in those arrays (for `_tally`,
    once the caller has them on the host).
    """
    W, L = int(k_l2.shape[0]), int(k_l2.shape[1])
    n_chunks = max(1, -(-L // max(1, chunk)))
    width = -(-L // n_chunks)
    order = lane_order(np.asarray(k_l2[0]), np.asarray(s_l2[0]))
    slices = []
    for c in range(0, L, width):
        idx = order[c:c + width]
        pad = width - len(idx)
        if pad:
            idx = np.concatenate([idx, np.repeat(idx[-1:], pad)])
        slices.append((idx, pad))
    rows, dispatches = [], []
    for w in range(W):
        pw_w = jax.tree.map(lambda x: x[w], spw)
        chunks = []
        for c, (idx, pad) in enumerate(slices):
            with obs.span("repro.sweep.dispatch", workload=w, chunk=c,
                          lanes=width - pad, width=width) as sp:
                out = _packet_lanes(
                    pw_w, k_l2[w, idx], s_l2[w, idx], m_nodes, ring,
                    None if chaos is None else jax.tree.map(
                        lambda x: jnp.asarray(x)[idx], chaos),
                    step_impl=step_impl)
                obs.stamp_when_ready("repro.sweep.device", out[1])
            dispatches.append((sp, (w, idx[:width - pad]), [(w, idx)]))
            chunks.append(jax.tree.map(
                lambda x: x[:width - pad] if pad else x, out))
        rows.append(jax.tree.map(lambda *x: jnp.concatenate(x), *chunks))
    gathered = jax.tree.map(lambda *x: jnp.stack(x), *rows)
    inv = jnp.asarray(np.argsort(order, kind="stable"))
    lanes, segs = jax.tree.map(lambda x: x[:, inv], gathered)
    segs.copy_to_host_async()
    return lanes, segs, dispatches


def _place_cohort_lanes(k_l2, s_l2, chaos=None):
    """The [W, L] lane operands padded to a device multiple with sentinel
    lanes and sharded over the devices: ``(k, s, chaos, sharding)``, the
    sharding None on one device."""
    L = int(k_l2.shape[1])
    pad = lane_padding(L)
    if pad:
        k_l2 = jnp.concatenate(
            [k_l2, jnp.repeat(k_l2[:, -1:], pad, axis=1)], axis=1)
        s_l2 = jnp.concatenate(
            [s_l2, jnp.repeat(s_l2[:, -1:], pad, axis=1)], axis=1)
        if chaos is not None:
            chaos = jax.tree.map(
                lambda x: jnp.concatenate([x, jnp.repeat(x[-1:], pad)]),
                chaos)
    sharding = cohort_lane_sharding(L + pad, pad=True)
    if sharding is not None:
        k_l2 = jax.device_put(k_l2, sharding)
        s_l2 = jax.device_put(s_l2, sharding)
        if chaos is not None:
            # chaos leaves are [L]: shard with the 1-D lane sharding that
            # matches the inner (lane) axis of the [W, L] operands
            chaos = jax.device_put(chaos, lane_sharding(L + pad, pad=True))
    return k_l2, s_l2, chaos, sharding


def _run_cohort_fused(spw, k_l2, s_l2, m_nodes, ring, n_real: int,
                      sharding=None, chaos=None, step_impl="xla"):
    """All W x L lanes, placed by `_place_cohort_lanes`, in one dispatch.

    Returns ``(Metrics, segments, dispatches)`` as `_run_cohort_chunks`
    does, the arrays over the padded lane axis; `n_real` lanes are real.
    """
    W, n_lanes = int(k_l2.shape[0]), int(k_l2.shape[1])
    with obs.span("repro.sweep.dispatch", chunk=0, lanes=W * n_real,
                  width=W * n_lanes) as sp:
        lanes, segs = per_device_lanes(
            _packet_cohort_lanes, sharding, m_nodes, ring, step_impl)(
                spw, k_l2, s_l2, chaos)
        segs.copy_to_host_async()
        obs.stamp_when_ready("repro.sweep.device", segs)
    chips = 1 if sharding is None else len(sharding.device_set)
    q = n_lanes // chips
    per_chip = [slice(c * q, (c + 1) * q) for c in range(chips)]
    # the pallas step runs one loop per member, the XLA step one for all
    loops = ([(w, b) for b in per_chip for w in range(W)]
             if step_impl == "pallas" else [(slice(None), b)
                                            for b in per_chip])
    return lanes, segs, [(sp, (slice(None), slice(0, n_real)), loops)]


def run_cohort_grid(cohort, ks: Sequence[float] = PAPER_SCALE_RATIOS,
                    s_props: Sequence[float] = PAPER_INIT_PROPS,
                    mode: str = "auto",
                    chunk_lanes: int | None = None,
                    chaos: ChaosConfig | None = None,
                    on_budget_exhausted: str = "raise",
                    step_impl: str = "xla") -> dict:
    """Per-workload [K, S] Metrics for every member of a `WorkloadCohort`,
    computed as ONE batched study over the stacked workload axis.

    Returns ``{name: Metrics}`` with leaves of shape [len(ks), len(s_props)]
    (``[K, S, C]`` when `chaos` carries a C-long fault-parameter axis) —
    each entry identical (lane for lane) to
    ``run_packet_grid(wl, ks, s_props, dtype=cohort.dtype)``, because the
    cohort kernel batches the same scan engine over an extra workload axis
    and per-lane results are independent of dispatch grouping (the cohort
    equivalence suite pins this bitwise in both dtypes). The chaos lane
    stream is shared across members (lane ids are assigned per grid cell,
    not per workload), so cohort and per-workload runs agree exactly and
    cross-workload comparisons use common random numbers.

    Modes are the sweep layouts applied to the [W, L] study: ``"chunked"``
    dispatches sorted [W, width] blocks, ``"fused"`` runs one padded +
    sharded program, ``"seq"`` delegates to per-workload sequential
    dispatch (the pre-cohort driver layout — the measured-fastest choice
    for studies too small to amortize batching; see the no-while-kernel
    note above), and ``"auto"`` resolves from the TOTAL experiment count
    W * L (`resolve_mode`). The legacy vmap_k/vmap_s layouts have no
    cohort form. Init proportions are converted per member (s depends on
    each workload's mean runtime), so the [W, L] init-time operand
    genuinely varies across the workload axis.
    """
    if chaos_is_inert(chaos):
        chaos = None        # zero-rate config: run the exact pre-chaos trace
    K, S = len(ks), len(s_props)
    W = cohort.n_workloads
    resolved = resolve_mode(mode, K * S, W, step_impl)
    if resolved in ("vmap_k", "vmap_s"):
        raise ValueError(
            f"mode {resolved!r} has no cohort layout; use run_packet_grid "
            f"per workload for the legacy column/row batchings")
    chips = jax.device_count() if resolved == "fused" else 1
    with obs.span("repro.study", workloads=W, ks=K, s_props=S,
                  layout=resolved, chips=chips):
        if resolved == "seq":
            return {name: run_packet_grid(
                        wl, ks, s_props, dtype=cohort.dtype, mode="seq",
                        chaos=chaos, on_budget_exhausted=on_budget_exhausted,
                        step_impl=step_impl)
                    for name, wl in zip(cohort.names, cohort.workloads)}
        with precision.dtype_scope(cohort.dtype):
            return _run_cohort(cohort, ks, s_props, resolved, chunk_lanes,
                               chaos, on_budget_exhausted, step_impl)


def _run_cohort(cohort, ks, s_props, resolved: str, chunk_lanes, chaos,
                on_budget_exhausted: str, step_impl: str) -> dict:
    """`run_cohort_grid`'s chunked and fused layouts, inside its span and
    dtype scope: lane operands, the dispatches, the gather."""
    K, S, W = len(ks), len(s_props), cohort.n_workloads
    dtype = cohort.dtype
    with obs.span("repro.study.prepare"):
        spw = cohort.pack()
        m_nodes, ring = cohort.m_nodes, cohort.ring
        ks_arr = jnp.asarray(ks, dtype)
        s_mat = jnp.stack([jnp.asarray(
            [wl.init_time_for_proportion(p) for p in s_props], dtype)
            for wl in cohort.workloads])                    # [W, S]
        k_l2 = jnp.broadcast_to(jnp.repeat(ks_arr, S), (W, K * S))
        s_l2 = jnp.tile(s_mat, (1, K))
        chaos_l, C = (None, 1) if chaos is None else chaos_lane_grid(
            chaos, K * S, dtype)
        if C > 1:
            k_l2 = jnp.repeat(k_l2, C, axis=1)
            s_l2 = jnp.repeat(s_l2, C, axis=1)
        sharding = None
        if resolved == "fused":
            k_l2, s_l2, chaos_l, sharding = _place_cohort_lanes(
                k_l2, s_l2, chaos_l)
    L = K * S * C
    if resolved == "chunked":
        lanes, segs, dispatches = _run_cohort_chunks(
            spw, k_l2, s_l2, m_nodes, ring,
            max(1, int(chunk_lanes or CHUNK_LANES)), chaos_l, step_impl)
    else:                   # fused
        lanes, segs, dispatches = _run_cohort_fused(
            spw, k_l2, s_l2, m_nodes, ring, L, sharding, chaos_l, step_impl)
    with obs.span("repro.study.gather"):
        lanes, segs = jax.tree.map(np.asarray, (lanes, segs))
        for sp, real, loops in dispatches:
            _tally(sp, spw.n_jobs, lanes.n_groups[real],
                   [segs[i] for i in loops])
        shape = (W, K, S) if C == 1 else (W, K, S, C)
        grids = jax.tree.map(
            lambda x: x[:, :L].reshape(shape + x.shape[2:]), lanes)
        out = {name: jax.tree.map(lambda x, w=w: x[w], grids)
               for w, name in enumerate(cohort.names)}
        for name, m in out.items():
            _enforce_budget(m, on_budget_exhausted,
                            f"run_cohort_grid[{name}]", ks, s_props)
    return out


def run_packet_grid(wl: Workload,
                    ks: Sequence[float] = PAPER_SCALE_RATIOS,
                    s_props: Sequence[float] = PAPER_INIT_PROPS,
                    dtype=jnp.float32,
                    vmap_s: bool = False,
                    vmap_k: bool = False,
                    mode: str = "auto",
                    chunk_lanes: int | None = None,
                    chaos: ChaosConfig | None = None,
                    on_budget_exhausted: str = "raise",
                    step_impl: str = "xla") -> Metrics:
    """Metrics over the (scale ratio x init proportion) grid of one workload.

    Returns a Metrics pytree whose leaves have shape [len(ks), len(s_props)],
    or ``[len(ks), len(s_props), C]`` when `chaos` carries a C-long
    fault-parameter axis (`chaos_axis_len`) — the chaos axis is a third
    lane dimension, swept at full batched throughput. Lane ids are assigned
    in grid order before any dispatch-layout reshuffling, so seq, chunked
    and fused produce bit-identical chaos draws. `on_budget_exhausted`
    ("raise" | "warn" | "ignore") governs lanes whose schedules were
    truncated by the event budget (`Metrics.budget_exhausted`).

    Modes (see the module docstring for the layouts): ``"seq"``,
    ``"chunked"``, ``"fused"``, ``"auto"`` (device/lane-count heuristic via
    `resolve_mode`), plus the legacy ``vmap_k=True`` / ``vmap_s=True``
    column/row batchings kept for A/B comparison (passing both is an
    error — previously vmap_k silently won).

    All paths share module-level compile caches keyed on workload shape, so
    repeated calls (and the paper's 6 same-shape workflows) never retrace.
    jit caches are additionally keyed on dtype (via input avals and the x64
    trace context), so float32 and float64 sweeps coexist without retracing
    each other.

    `dtype=jnp.float64` is the precision opt-in: the whole sweep runs inside
    `precision.dtype_scope`, leaving the session's global x64 state alone.
    `chunk_lanes` overrides the chunked-mode dispatch width (default
    CHUNK_LANES).
    """
    if vmap_k and vmap_s:
        raise ValueError("vmap_k=True and vmap_s=True are mutually "
                         "exclusive batching layouts; pass at most one "
                         "(or use mode='fused' for the full lane axis)")
    if (vmap_k or vmap_s) and mode != "auto":
        raise ValueError("pass either mode= or the legacy vmap_k/vmap_s "
                         "flags, not both")
    if chaos is not None and (vmap_k or vmap_s):
        raise ValueError("chaos sweeps have no vmap_k/vmap_s layout; use "
                         "mode='seq'/'chunked'/'fused'")
    _check_step_impl(step_impl)
    if step_impl == "pallas" and (vmap_k or vmap_s):
        raise ValueError("the legacy vmap_k/vmap_s layouts are XLA-only; "
                         "use mode='seq'/'chunked'/'fused' with "
                         "step_impl='pallas'")
    if chaos_is_inert(chaos):
        chaos = None        # zero-rate config: run the exact pre-chaos trace
    K, S = len(ks), len(s_props)
    if vmap_k:
        mode = "vmap_k"
    elif vmap_s:
        mode = "vmap_s"
    else:
        mode = resolve_mode(mode, K * S * chaos_axis_len(chaos),
                            step_impl=step_impl)

    with precision.dtype_scope(dtype):
        pw = pack_workload(wl, dtype)
        m_nodes = int(wl.params.nodes)
        ring = resolve_ring(m_nodes, pw.n_jobs)
        s_vals = jnp.asarray(
            [wl.init_time_for_proportion(p) for p in s_props], dtype)
        ks_arr = jnp.asarray(ks, dtype)

        if mode == "vmap_k":
            cols = [_packet_k_column(pw, ks_arr, s, m_nodes, ring)
                    for s in s_vals]
            stacked = jax.tree.map(lambda *x: jnp.stack(x, axis=1), *cols)
            return jax.tree.map(np.asarray, stacked)
        if mode == "vmap_s":
            rows = [_packet_s_row(pw, k, s_vals, m_nodes, ring)
                    for k in ks_arr]
            stacked = jax.tree.map(lambda *x: jnp.stack(x, axis=0), *rows)
            return jax.tree.map(np.asarray, stacked)

        chaos_l, C = (None, 1) if chaos is None else chaos_lane_grid(
            chaos, K * S, dtype)
        shape = (K, S) if C == 1 else (K, S, C)
        if mode == "seq":
            if chaos is None:
                cells = [_packet_one(pw, k, s, m_nodes, ring,
                                     step_impl=step_impl)
                         for k in ks_arr for s in s_vals]
            else:
                # the scan engine, one flat lane at a time — same engine
                # and lane ids as the batched layouts, so chaos draws and
                # float rounding match the chunked/fused modes exactly
                cells = [_packet_one(pw, ks_arr[i // (S * C)],
                                     s_vals[(i // C) % S], m_nodes, ring,
                                     _chaos_cell(chaos_l, i),
                                     step_impl=step_impl)
                         for i in range(K * S * C)]
            stacked = jax.tree.map(lambda *x: jnp.stack(x), *cells)
            out = jax.tree.map(
                lambda x: np.asarray(x).reshape(shape + x.shape[1:]),
                stacked)
            _enforce_budget(out, on_budget_exhausted, "run_packet_grid",
                            ks, s_props)
            return out

        # batched lane layouts over the scan engine
        k_lanes = jnp.repeat(ks_arr, S * C)
        s_lanes = jnp.repeat(jnp.tile(s_vals, K), C)
        if mode == "chunked":
            lanes = _run_lane_chunks(pw, k_lanes, s_lanes, m_nodes, ring,
                                     max(1, int(chunk_lanes or CHUNK_LANES)),
                                     chaos_l, step_impl)
        else:                       # fused
            lanes = _run_lanes_fused(pw, k_lanes, s_lanes, m_nodes, ring,
                                     chaos_l, step_impl)
        out = jax.tree.map(
            lambda x: np.asarray(x).reshape(shape + x.shape[1:]), lanes)
        _enforce_budget(out, on_budget_exhausted, "run_packet_grid",
                        ks, s_props)
        return out


def run_window_oracle(pw: PackedWorkload,
                      ks: Sequence[float],
                      s_init: float,
                      m_nodes: int,
                      ring: int | None = None,
                      mode: str = "auto",
                      chunk_lanes: int | None = None,
                      chaos: ChaosConfig | None = None,
                      on_budget_exhausted: str = "raise",
                      step_impl: str = "xla") -> Metrics:
    """One control tick of the streaming service: all candidate scale
    ratios on a pre-packed workload window, as one batched lane program.

    This is `run_packet_grid` re-cut for the monitor → decide → actuate
    loop of `repro.service`: the caller owns packing (windows arrive
    already packed, via `pack_workload` on a `slice_window` output) and
    passes ONE init time `s_init` in seconds (typically from the monitor's
    windowed runtime signal, not a whole s_props axis), so the returned
    Metrics leaves are [len(ks)] — the tick's tuning curve. Because the
    windowing layer holds `window_jobs` fixed, every tick shares the
    packed shapes and the module-level jit caches (`_packet_lanes` /
    `_packet_one`): the lane program traces on the first tick and only
    dispatches afterwards.

    `chaos` makes the tick fault-aware: a `ChaosConfig` whose fault
    parameters carry a C-long chaos lane axis (`chaos_axis_len`) expands
    the tick to one fused [K * C] lane program and the returned leaves to
    ``[len(ks), C]`` — per candidate k, the wait / lost_work /
    useful_util / requeued_jobs cells across every fault regime, from ONE
    dispatch. Lane ids follow `chaos_lane_grid` grid order (k-major,
    chaos-minor), exactly the ids `run_packet_grid(ks, s_props=[s],
    chaos=...)` assigns, so the oracle's [K, C] block is bitwise the
    grid driver's ``[:, 0, :]`` chaos column (tests/test_service.py pins
    this in both dtypes). An inert config (zero failure and straggler
    rates) is normalized to None and runs the exact fault-free program;
    a scalar active config keeps [K] leaves (C == 1).

    Dtype follows the packed window (pack under `precision.dtype_scope`
    for float64); the sweep re-enters that scope here so a float64 service
    loop never leaks global x64 state. Modes as in `run_packet_grid`
    minus the legacy vmap layouts ("auto" resolves over the K * C lanes
    of this single tick).
    """
    dtype = np.dtype(pw.submit.dtype)
    K = len(ks)
    if K < 1:
        raise ValueError("run_window_oracle needs at least one candidate k")
    if chaos_is_inert(chaos):
        chaos = None        # zero-rate config: run the exact pre-chaos trace
    C = chaos_axis_len(chaos)
    resolved = resolve_mode(mode, K * C, step_impl=step_impl)
    if resolved in ("vmap_k", "vmap_s"):
        raise ValueError(
            f"mode={resolved!r} is a grid layout; the window oracle has a "
            "single lane axis — use 'auto', 'seq', 'chunked' or 'fused'")
    with precision.dtype_scope(dtype):
        m_nodes = int(m_nodes)
        ring = resolve_ring(m_nodes, pw.n_jobs) if ring is None else int(ring)
        chaos_l = (None if chaos is None
                   else chaos_lane_grid(chaos, K, dtype)[0])
        k_lanes = jnp.repeat(jnp.asarray(ks, dtype), C)
        s_lanes = jnp.full((K * C,), s_init, dtype)
        if resolved == "seq":
            cells = [_packet_one(pw, k_lanes[i], s_lanes[i], m_nodes, ring,
                                 None if chaos_l is None
                                 else _chaos_cell(chaos_l, i),
                                 step_impl=step_impl)
                     for i in range(K * C)]
            lanes = jax.tree.map(lambda *x: jnp.stack(x), *cells)
        elif resolved == "chunked":
            lanes = _run_lane_chunks(pw, k_lanes, s_lanes, m_nodes, ring,
                                     max(1, int(chunk_lanes or CHUNK_LANES)),
                                     chaos_l, step_impl)
        else:                       # fused
            lanes = _run_lanes_fused(pw, k_lanes, s_lanes, m_nodes, ring,
                                     chaos_l, step_impl)
        shape = (K,) if C == 1 else (K, C)
        out = jax.tree.map(
            lambda x: np.asarray(x).reshape(shape + x.shape[1:]), lanes)
        _enforce_budget(out, on_budget_exhausted, "run_window_oracle", ks,
                        axis_names=("i_k", "i_chaos"))
        return out


# --------------------------------------------------------------------------
# Rigid baselines (repro.core.schedulers) over a cohort.
# --------------------------------------------------------------------------

class BaselineRun(NamedTuple):
    """One rigid scheduler's runs of one flow, one per init proportion."""
    metrics: Metrics          # leaves [S]
    start_t: np.ndarray       # [S, N] each job's start


@partial(jax.jit, static_argnames=("scheduler", "m_nodes", "ring",
                                   "backfill_depth"))
def _rigid_cohort_lanes(spw, s_l2, scheduler, m_nodes, ring, backfill_depth):
    """[W]-stacked workloads x [W, S] init times, one rigid scheduler: one
    program of W * S lanes (the nesting of `_packet_cohort_lanes`).

    Returns ``(Metrics, start_t, RigidCounts)``, leaves [W, S] and start
    times [W, S, N]."""
    def lane(pw, s):
        res, counts = simulate_rigid(pw, s, m_nodes, scheduler,
                                     backfill_depth=backfill_depth, ring=ring)
        return (efficiency_metrics(pw.submit, res, m_nodes,
                                   pw.t_last_submit), res.start_t, counts)

    return jax.vmap(jax.vmap(lane, in_axes=(None, 0)))(spw, s_l2)


def _tally_rigid(sp, n_jobs: int, counts, backfill_depth: int) -> None:
    """Count a rigid dispatch on its span, from its counts on the host.

    ``lane_events``: 2N per lane (each job is submitted and completes
    once); ``lane_steps_run``: lanes x the outer trips of its longest lane
    (a vmapped lane trips, masked, until the last one drains);
    ``backfill_slots``: candidate trips run (`backfill_depth` per pass);
    ``backfill_candidates``: the trips that held a waiting job."""
    lanes = int(counts.trips.size)
    sp.count("lane_events", 2 * n_jobs * lanes)
    sp.count("lane_steps_run", lanes * int(counts.trips.max()))
    sp.count("backfill_slots", backfill_depth * int(counts.passes.sum()))
    sp.count("backfill_candidates", int(counts.candidates.sum()))


def run_cohort_baselines(cohort, s_props: Sequence[float] = PAPER_INIT_PROPS,
                         schedulers: Sequence[str] = RIGID_SCHEDULERS,
                         backfill_depth: int = 64,
                         on_budget_exhausted: str = "raise") -> dict:
    """Rigid-scheduler runs of every member of a `WorkloadCohort`.

    Returns ``{name: {scheduler: BaselineRun}}``: per init proportion the
    `Metrics` (with `ok` and `budget_exhausted`) and each job's start time.
    Each scheduler runs as one program over the cohort's [W] stacked flows
    x S init proportions (converted per member, as in `run_cohort_grid`);
    a lane's results do not depend on what shares its dispatch, so each
    equals the single-flow `simulate_fcfs` / `simulate_backfill` run.
    """
    W, S = cohort.n_workloads, len(s_props)
    with obs.span("repro.baselines", workloads=W, s_props=S,
                  schedulers=",".join(schedulers)), \
            precision.dtype_scope(cohort.dtype):
        spw = cohort.pack()
        s_l2 = jnp.asarray([[wl.init_time_for_proportion(p) for p in s_props]
                            for wl in cohort.workloads], cohort.dtype)
        dispatched = []
        for sched in schedulers:
            with obs.span("repro.sweep.dispatch", scheduler=sched,
                          lanes=W * S, width=W * S) as sp:
                out = _rigid_cohort_lanes(spw, s_l2, sched, cohort.m_nodes,
                                          cohort.ring, backfill_depth)
                obs.stamp_when_ready("repro.sweep.device", out)
            dispatched.append((sched, sp, out))
        runs = {name: {} for name in cohort.names}
        for sched, sp, out in dispatched:
            metrics, start_t, counts = jax.tree.map(np.asarray, out)
            _tally_rigid(sp, spw.n_jobs, counts, backfill_depth)
            for w, name in enumerate(cohort.names):
                m = jax.tree.map(lambda x, w=w: x[w], metrics)
                _enforce_budget(m, on_budget_exhausted,
                                f"run_cohort_baselines[{name}, {sched}]")
                runs[name][sched] = BaselineRun(m, start_t[w])
    return runs


def run_baselines(wl: Workload, s_props: Sequence[float] = PAPER_INIT_PROPS,
                  dtype=jnp.float32) -> dict[str, Metrics]:
    """FCFS and EASY-backfill metrics per init proportion (rigid jobs): the
    one-member call of `run_cohort_baselines`. `dtype=jnp.float64` opts
    into the scoped x64 mode, as in `run_packet_grid`."""
    cohort, = group_workloads({"flow": wl}, dtype)
    return {sched: run.metrics
            for sched, run in run_cohort_baselines(cohort, s_props)
            ["flow"].items()}


class PlateauResult(NamedTuple):
    """`plateau_threshold` output: the tuned scale ratio AND the plateau
    level it converged to, so callers can sanity-check flip-prone cells
    (a float32 near-tie cascade moves `plateau`, not just `threshold`)."""
    threshold: float    # smallest k after which avg_wait stays near plateau
    plateau: float      # the large-k plateau value (median of the tail)


def plateau_threshold(ks, avg_wait, rel_tol: float = 0.05,
                      abs_tol: float | None = None,
                      plateau_tail: int = 5) -> PlateauResult:
    """The paper's actionable output: the smallest scale ratio after which
    the average queue time stays within tolerance of its large-k plateau.

    `ks` need not arrive sorted — both arrays are sorted together by k
    (the plateau is a large-k property, so order matters); mismatched or
    empty inputs raise. The tolerance band is
    ``rel_tol * max(plateau, 1) + abs_tol`` where `abs_tol` defaults to
    ``FLOAT32_AVG_WAIT_RTOL * max(plateau, 1)`` — the measured float32
    rounding envelope from the BENCH_dtype study — instead of the previous
    hard-coded 1.0 s, so the slack scales with the metric rather than
    assuming second-scale waits.
    """
    ks = np.atleast_1d(np.asarray(ks, np.float64))
    w = np.atleast_1d(np.asarray(avg_wait, np.float64))
    if ks.ndim != 1 or ks.shape != w.shape:
        raise ValueError(f"ks and avg_wait must be equal-length 1-D arrays, "
                         f"got shapes {ks.shape} and {w.shape}")
    if ks.size == 0:
        raise ValueError("plateau_threshold needs at least one scale ratio")
    order = np.argsort(ks, kind="stable")
    ks, w = ks[order], w[order]
    tail = max(1, min(int(plateau_tail), len(w)))
    plateau = float(np.median(w[-tail:]))
    ref = max(plateau, 1e-9)
    if abs_tol is None:
        abs_tol = FLOAT32_AVG_WAIT_RTOL * max(ref, 1.0)
    good = np.abs(w - plateau) <= rel_tol * max(ref, 1.0) + abs_tol
    # find first index from which all subsequent are good
    for i in range(len(ks)):
        if good[i:].all():
            return PlateauResult(float(ks[i]), plateau)
    return PlateauResult(float(ks[-1]), plateau)

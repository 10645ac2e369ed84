"""Fixed-shape discrete-event simulator of the Packet algorithm (paper §5-6).

This is the JAX/TPU-native replacement for the paper's Alea-based JMS model:
one `lax.while_loop` program with a small, fixed set of state arrays, jit-able
and `vmap`-able over the experiment grid (scale ratio x init proportion), so
the paper's 1332-experiment study runs as a handful of batched XLA programs
instead of 1332 sequential Java simulations.

Why it vectorizes: the Packet algorithm always drains the *entire* selected
queue into one group (paper Step 3), so each per-type queue is a contiguous
window [head_j, tail_j) over that type's jobs in submit order. Queue
aggregates are O(1) reads of precomputed per-type prefix sums, and nodes are
fungible counts (moldable linear-speedup groups on a homogeneous cluster), so
the whole simulator state is ~a dozen small arrays.

Events: (a) job submission, (b) group completion (nodes released). On every
event the greedy scheduling pass (paper Steps 1-5) runs until it is blocked.

Complexity
----------
The event loop runs O(N) events and forms G <= N groups. The original
("reference") implementation wrote per-job metrics eagerly: every group
formation built an `in_grp` mask over all N jobs and did two masked [N]
writes, so the whole simulation cost O(G * N) — dominated by metric
bookkeeping, not scheduling.

The production path (`simulate_packet`) instead keeps a bounded *group log*:
forming a group appends one O(1) record

    key = jtype * (N + 1) + tail_rank,  (t_start, m_grp, head_prefix_work)

to a flat log of capacity N (every group drains >= 1 job, so G <= N). Inside
a type, group tails are strictly increasing and partition [0, count_j), so a
job of type j and rank r belongs to the type-j group with the smallest
tail > r. One post-loop `argsort` of the log keys plus one vectorized
`searchsorted` of each job's `jtype * (N + 1) + rank` recovers every job's
group — and with it `start_t` and `run_start_t` — in O(N log N) total.

Per-event work is therefore O(H + RING) (queue weights over H types plus the
running-group ring), and the whole simulation is O(N * (H + RING) + N log N)
instead of O(N * G). The ring itself is sized `min(M, N)` (every running
group holds >= 1 node, so at most M run concurrently) rather than a fixed
512, which cuts the loop-carried state ~5x for the paper's homogeneous
M = 100 flows; see `resolve_ring`.

Two equivalent engines expose that loop:

  * `simulate_packet` — `lax.while_loop` with a nested scheduling loop and
    the group log carried as [N] state. Fastest for ONE experiment (exact
    early exit per event); this is the sweep's mode="seq" path.
  * `simulate_packet_scan` — a branchless single-step-kind `lax.scan` over
    a precomputed event budget (~3N, segmented early exit) that EMITS log
    records as scan outputs instead of scattering into [N] carry. This is
    the vmap-friendly form: batched lanes cost about the same per
    experiment as sequential dispatch (the vmapped while engine lost ~16x
    on CPU dragging [lanes, N] log state through lockstep iterations); the
    sweep's chunked/fused modes build on it. See repro.core.sweep.

    The PackedWorkload is an *operand*, never a closure, and every one of
    its array leaves (including the scalar `t_last_submit`) is safe to
    batch: ``jax.vmap(simulate_packet_scan, in_axes=(0, 0, 0, None, None))``
    over a `repro.core.cohort.stack_workloads`-stacked pytree runs W
    same-static workloads in one program — the cohort layer of the sweep
    (`run_cohort_grid`) nests exactly that over the per-lane vmap. Only the
    aux statics (n_types, n_jobs) must agree across the batch; `cohort_key`
    groups workloads so they do.

Chaos (fault injection)
-----------------------
Both engines accept an optional `ChaosConfig` operand porting the host-side
`repro.cluster.scheduler.ClusterSim` fault semantics into the fixed-shape
vectorized model, so MTBF / checkpoint-period / straggler parameters become
sweep lane axes (see repro.core.sweep):

  * per-group exponential chip-slice failures — every group formation g
    consumes one row of a PRECOMPUTED per-lane uniform stream
    ``u_all = uniform(fold_in(PRNGKey(seed), lane), (N + max_requeues, 2))``
    and draws ``t_fail = -log(u2) * (mtbf * 3600) / m``. The stream is
    indexed by the group counter, never by step position, so seq / chunked /
    fused dispatch layouts see bit-identical draws (the differential suite
    pins this);
  * failures resolve at group END, exactly like ClusterSim's `_maybe_fail`:
    the group holds its chips until the scheduled finish, work past the
    last checkpoint (``floor(run_done / ckpt_period) * ckpt_period``) is
    lost, and only the checkpointed fraction counts as useful;
  * straggler stretch + deadline kill — with prob `straggler_prob` the run
    span stretches by `straggler_factor`; if the stretched duration exceeds
    ``straggler_deadline x expected``, the group is killed at the deadline
    and only ``(deadline - s) * m / stretch`` of work is credited;
  * requeue — the uncredited remainder re-enters the queue as its TRUE
    member set. A formed group of type j is always one contiguous rank
    span [qlo, tail) of that type (window + previously requeued pool), so
    ClusterSim's per-member credit walk (`_requeue`: credit members in
    order, requeue whoever keeps > 1e-9 of work) reduces to ONE binary
    search (`_credit_cut`) over the type's work prefix sums `tj_prefw[j]`:
    the cut rank is the first member the credit does not finish, the
    remnant is the rank span [cut, tail) with a done-work RESIDUAL
    carried for the partially credited head member. To keep the scan
    step's state writes flat, formation only STASHES the span identity
    in the group's ring slot — an int32 code ``1 + qlo*(N+1) + tail``
    in `grp_rem_cnt` plus the available credit in `grp_rem_w` — and the
    walk itself is DEFERRED to the finish event (`_resolve_remnant`),
    which is also when ClusterSim credits members. The per-type POOL
    keeps exact work/oldest aggregates (pool_w / pool_oldest) plus ONE
    packed int32 `pool_code` carrying span head, fragmented bit and
    member count (`_pool_decode`); the partially-credited head member's
    done-work residual is not stored at all — a non-fragmented pool is
    one contiguous span, so the next formation recovers it as span work
    minus pool_w. Memory/budget cost: O(H + ring) extra scalars — three
    [H] fields and three [ring] fields (write parity with the
    aggregate pool this replaces), never [N] member state, so the scan
    engine's vmap shape and `event_budget(N, R)` are unchanged (a
    requeue batch still funds at most one extra formation + finish). Count, oldest-submit and queue weight of a remnant are
    exact whenever the pool is one rank span credited oldest-first
    (always, in every differential hand case); if two same-type groups
    finish with remnants before the next formation, or a remnant
    returns after newer jobs already drained past it, the pool is marked
    FRAGMENTED and that one batch falls back to the PR-5 aggregate upper
    bound (all members requeued, group-oldest; encoded as a NEGATED
    count in the ring stash) — work stays exact and the flag clears at
    the type's next formation. Rank order equals ClusterSim's append
    order except when jobs submitted during the failed group's run are
    themselves split by the credit;
  * bounded injection — at most `max_requeues` (default N) requeues are
    injected per lane, so group count stays <= N + max_requeues and
    `event_budget(N, max_requeues)` stays analytic. Hitting a genuinely
    too-small user budget is reported as ``budget_exhausted=True`` in the
    result instead of silently truncating the schedule.

With ``chaos=None`` (the default) none of this is traced and the engines
are bitwise-identical to their pre-chaos form; a ChaosConfig with
``mtbf_chip_hours=0, straggler_prob=0`` is also bitwise-identical (every
fault predicate is False and all accumulator increments are exact zeros).

Precision
---------
The simulation dtype is set at `pack_workload(..., dtype=...)` and carried
by every time/accumulator array; float64 requires the scoped opt-in in
`repro.core.precision` (never a global flag flip). Measured against the
float64 reference over the full 37 x 6 paper grid
(benchmarks/results/BENCH_dtype.json, 5000-job flows):

  * homogeneous flows and FCFS stay at rounding level in float32 (max
    same-schedule relative deviation ~7e-3 on waits, ~1e-6 .. 2e-6 on
    utilizations and FCFS metrics), with <= 3 decision flips per 222 cells;
  * heterogeneous 5000-job flows are float32-CHAOTIC: 77-83% of grid cells
    resolve a near-tie in queue weights or event order differently and the
    schedule diverges wholesale (up to ~650% on per-cell avg_wait; EASY
    backfill flips too, up to ~25%). Per-cell metric work on long-horizon
    heterogeneous workloads should use the float64 opt-in.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packet, precision
from repro.workload.lublin import Workload

INF = jnp.inf
RING = 512           # static fallback ring size (used when M is traced)
CREDIT_EPS = 1e-9    # ClusterSim _requeue's "fully credited" threshold


def resolve_ring(m_nodes, n_jobs: int, ring: int | None = None) -> int:
    """Ring size for the running-group buffer.

    Every running group (or rigid job) holds at least one node, so at most
    `min(M, N)` can run concurrently. When `m_nodes` is a concrete Python or
    NumPy scalar we size the ring exactly; under tracing (e.g. M itself is a
    vmap axis) we fall back to the static `RING` cap.
    """
    if ring is not None:
        return max(1, int(ring))
    try:
        m = int(m_nodes)
    except Exception:       # traced value — no concrete M at trace time
        return max(1, min(RING, n_jobs)) if n_jobs else 1
    return max(1, min(m, n_jobs if n_jobs else m))


@dataclasses.dataclass(frozen=True)
class PackedWorkload:
    """Device-resident, per-type-indexed form of a Workload.

    H = n_types, N = n_jobs. Per-type tables are rank-indexed (rank r =
    r-th job of that type in submit order), padded with +inf / 0.
    """
    submit: jnp.ndarray      # [N]  global submit order
    work: jnp.ndarray        # [N]  w_i = e_i * n_i
    jtype: jnp.ndarray       # [N]
    rank: jnp.ndarray        # [N]  rank of job i within its type
    cumw: jnp.ndarray        # [N]  per-type prefix work *before* job i
    nodes: jnp.ndarray       # [N]  rigid node request (baselines only)
    runtime: jnp.ndarray     # [N]  e_i on n_i nodes (baselines only)
    tj_submit: jnp.ndarray   # [H, N]   submit of type j's rank-r job (+inf pad)
    tj_prefw: jnp.ndarray    # [H, N+1] prefix sums of work per type
    t_last_submit: jnp.ndarray  # scalar: metric window end (paper §3)
    n_types: int
    n_jobs: int


def _pw_flatten(pw: PackedWorkload):
    children = (pw.submit, pw.work, pw.jtype, pw.rank, pw.cumw, pw.nodes,
                pw.runtime, pw.tj_submit, pw.tj_prefw, pw.t_last_submit)
    return children, (pw.n_types, pw.n_jobs)


def _pw_unflatten(aux, children):
    return PackedWorkload(*children, n_types=aux[0], n_jobs=aux[1])


jax.tree_util.register_pytree_node(PackedWorkload, _pw_flatten, _pw_unflatten)


def pack_workload(wl: Workload, dtype=jnp.float32) -> PackedWorkload:
    """Build the per-type-indexed tables with numpy segment prefix sums.

    A stable sort by type turns each type into one contiguous segment, so
    per-type ranks and prefix work are plain offset arithmetic on one global
    cumsum — no Python loop over jobs.

    `dtype` selects the simulation precision for every float table and, via
    the packed arrays, every downstream accumulator. float64 requires the
    explicit x64 opt-in (`repro.core.precision.dtype_scope`); requesting it
    outside a scope raises instead of silently truncating to float32.
    """
    dtype = precision.canonical_dtype(dtype)
    H, N = wl.params.n_types, wl.n_jobs
    jt = np.asarray(wl.jtype, np.int64)
    w = np.asarray(wl.work, np.float64)
    submit = np.asarray(wl.submit, np.float64)

    order = np.argsort(jt, kind="stable")
    jt_s = jt[order]
    w_s = w[order]
    counts = np.bincount(jt, minlength=H)
    seg_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(N)
    rank_s = pos - seg_start[jt_s]                      # rank within type
    cum = np.concatenate([[0.0], np.cumsum(w_s)])
    cumw_s = cum[pos] - cum[seg_start[jt_s]]            # prefix work in type

    rank = np.zeros(N, np.int32)
    cumw = np.zeros(N, np.float64)
    rank[order] = rank_s.astype(np.int32)
    cumw[order] = cumw_s

    tj_submit = np.full((H, N), np.inf)
    tj_submit[jt_s, rank_s] = submit[order]
    tj_prefw = np.zeros((H, N + 1), np.float64)
    tj_prefw[jt_s, rank_s + 1] = cumw_s + w_s
    # extend prefix sums into the padding so prefw[tail] is always valid
    # (work >= 0 makes each row nondecreasing, so a running max fills pads)
    tj_prefw = np.maximum.accumulate(tj_prefw, axis=1)

    f = lambda a: jnp.asarray(a, dtype)
    return PackedWorkload(
        submit=f(wl.submit), work=f(wl.work), jtype=jnp.asarray(wl.jtype, jnp.int32),
        rank=jnp.asarray(rank), cumw=f(cumw), nodes=jnp.asarray(wl.nodes, jnp.int32),
        runtime=f(wl.runtime), tj_submit=f(tj_submit), tj_prefw=f(tj_prefw),
        t_last_submit=f(wl.submit[-1]), n_types=H, n_jobs=N)


# --------------------------------------------------------------------------
# Chaos: fault-injection parameters (ported from cluster/scheduler.py).
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChaosConfig:
    """Fault-injection operand for the DES engines (see module docstring).

    The five fault parameters and `lane` are pytree children: scalars for a
    single run, or equal-length arrays when vmapped as a chaos lane axis
    (repro.core.sweep broadcasts them). `lane` is the dispatch-invariant
    per-lane stream id — the sweep overwrites it with the flat grid index,
    so a lane's failure draws do not depend on how lanes were chunked,
    sorted or padded. `seed` and `max_requeues` are static aux (they size
    the uniform stream and the event budget); ``max_requeues=None`` resolves
    to the job count N at simulation time.
    """
    mtbf_chip_hours: object = 0.0     # 0 = no failures (ClusterSim default)
    ckpt_period: object = 300.0
    straggler_prob: object = 0.0
    straggler_factor: object = 1.5
    straggler_deadline: object = 2.0
    lane: object = 0
    seed: int = 0
    max_requeues: int | None = None


def _chaos_flatten(c: ChaosConfig):
    children = (c.mtbf_chip_hours, c.ckpt_period, c.straggler_prob,
                c.straggler_factor, c.straggler_deadline, c.lane)
    return children, (c.seed, c.max_requeues)


def _chaos_unflatten(aux, children):
    return ChaosConfig(*children, seed=aux[0], max_requeues=aux[1])


jax.tree_util.register_pytree_node(ChaosConfig, _chaos_flatten,
                                   _chaos_unflatten)


def resolve_max_requeues(chaos: ChaosConfig | None, n_jobs: int) -> int:
    """Static requeue-injection budget R: 0 without chaos, N by default."""
    if chaos is None:
        return 0
    if chaos.max_requeues is None:
        return max(1, int(n_jobs))
    return max(0, int(chaos.max_requeues))


def chaos_is_inert(chaos: ChaosConfig | None) -> bool:
    """True when `chaos` cannot inject any fault: None, or concrete
    all-zero failure and straggler rates (e.g. the default ChaosConfig()).

    The sweep/cohort drivers normalize inert configs to None before
    compiling, so "chaos disabled" runs the exact pre-chaos programs —
    same engines, same event-budget shapes, bitwise-identical metrics —
    instead of a zero-rate chaos trace. Traced leaves (inside jit/vmap)
    are conservatively treated as active.
    """
    if chaos is None:
        return True
    try:
        mtbf = np.asarray(chaos.mtbf_chip_hours)
        prob = np.asarray(chaos.straggler_prob)
    except Exception:
        return False
    return bool(np.all(mtbf == 0) and np.all(prob == 0))


def chaos_uniforms(chaos: ChaosConfig, dtype, n_groups_cap: int):
    """The per-lane uniform stream: row g = (straggler draw, failure draw)
    of the g-th group FORMED in this lane. Precomputed outside the event
    loop and indexed by the group counter, so every dispatch layout (and
    both engines) consumes identical draws. Exposed for hand tests that
    re-derive expected fault outcomes."""
    key = jax.random.fold_in(jax.random.PRNGKey(chaos.seed),
                             jnp.asarray(chaos.lane, jnp.uint32))
    return jax.random.uniform(key, (max(1, int(n_groups_cap)), 2),
                              dtype=precision.canonical_dtype(dtype))


class _ChaosOutcome(NamedTuple):
    dur: jnp.ndarray        # effective duration (stretch/kill applied)
    failed: jnp.ndarray     # failure strikes before the (effective) end
    killed: jnp.ndarray     # straggler deadline kill (failure wins ties)
    ckpt_done: jnp.ndarray  # checkpointed run seconds at failure time
    credit: jnp.ndarray     # work credited toward completion (chip-seconds)
    lost: jnp.ndarray       # chip-seconds lost past the last checkpoint


def _chaos_outcome(chaos: ChaosConfig, u1, u2, inject, s, work, m_grp,
                   dur0, dtype, barrier: bool = True) -> _ChaosOutcome:
    """Per-group fault outcome, mirroring ClusterSim's _schedule/_finish.

    All branches are `jnp.where` with the no-fault value equal to the exact
    pre-chaos expression, so a zero ChaosConfig changes no bits. `inject`
    gates every fault (the bounded-requeue cap); precedence matches
    ClusterSim: a failure before the effective end wins over a deadline
    kill, which wins over plain completion. `barrier=False` drops the
    closing optimization barrier, which Mosaic cannot lower (the compiled
    packet_step kernel).
    """
    m_f = m_grp.astype(dtype)
    tiny = jnp.asarray(np.finfo(np.dtype(dtype)).tiny, dtype)
    prob = jnp.asarray(chaos.straggler_prob, dtype)
    factor = jnp.asarray(chaos.straggler_factor, dtype)
    s_dead = jnp.asarray(chaos.straggler_deadline, dtype)
    mtbf = jnp.asarray(chaos.mtbf_chip_hours, dtype)
    ckpt = jnp.asarray(chaos.ckpt_period, dtype)

    stretched = inject & (u1 < prob)
    dur_s = jnp.where(stretched, s + (work / m_f) * factor, dur0)
    deadline = s_dead * dur0                     # x expected duration
    killed = inject & (dur_s > deadline)
    dur = jnp.where(killed, deadline, dur_s)
    t_fail = -jnp.log(jnp.maximum(u2, tiny)) * (mtbf * 3600.0) / m_f
    failed = inject & (mtbf > 0) & (t_fail < dur)
    run_done = jnp.maximum(jnp.minimum(t_fail, dur) - s, 0.0)
    ckpt_done = jnp.floor(run_done / jnp.maximum(ckpt, tiny)) * ckpt
    stretch = jnp.where(stretched, factor, jnp.ones((), dtype))
    credit = jnp.where(
        failed, ckpt_done * m_f / stretch,
        jnp.where(killed, jnp.maximum(dur - s, 0.0) * m_f / stretch, work))
    lost = jnp.where(failed, (run_done - ckpt_done) * m_f,
                     jnp.zeros((), dtype))
    # Barrier the outputs so XLA cannot fuse this arithmetic into the
    # surrounding engine code (e.g. an FMA formed in one program but not
    # another): every downstream consumer sees fault quantities rounded
    # here, once. This pins HLO-level fusion only — LLVM may still
    # contract mul+add at codegen — so the hard bitwise-parity guarantee
    # for fault sweeps comes from all dispatch modes sharing the scan
    # engine (see sweep._packet_one), with the barrier keeping that
    # engine's scalar and vmapped compilations rounding alike.
    outs = (dur, failed, killed, ckpt_done, credit, lost)
    if barrier:
        outs = jax.lax.optimization_barrier(outs)
    return _ChaosOutcome(*outs)


class DesState(NamedTuple):
    t: jnp.ndarray            # current time
    next_sub: jnp.ndarray     # index of next submission (global order)
    head: jnp.ndarray         # [H] per-type queue window start (rank)
    tail: jnp.ndarray         # [H] per-type queue window end (rank)
    m_free: jnp.ndarray       # free nodes
    grp_end: jnp.ndarray      # [ring] completion time of running groups (+inf = free)
    grp_m: jnp.ndarray        # [ring] nodes held
    log_key: jnp.ndarray      # [N] group log: jtype * (N+1) + tail rank
    log_t: jnp.ndarray        # [N] group start time
    log_m: jnp.ndarray        # [N] group node count
    log_headw: jnp.ndarray    # [N] per-type prefix work at group head
    qlen_int: jnp.ndarray     # integral of queue length over [0, t_last_submit]
    busy_ns: jnp.ndarray      # busy node-seconds within the metric window
    useful_ns: jnp.ndarray    # useful node-seconds within the metric window
    n_groups: jnp.ndarray     # groups formed == next free log slot
    iters: jnp.ndarray        # diagnostic: outer loop iterations
    # chaos state (zeros / untouched when chaos is None)
    pool_w: jnp.ndarray       # [H] requeued remainder work per type
    pool_oldest: jnp.ndarray  # [H] oldest submit among requeued jobs (+inf)
    # packed span identity + count (0 == empty pool):
    #   (head_rank * 2 + fragmented) * (N + 1) + count        (_pool_decode)
    # The head member's done-work residual is NOT stored: a non-fragmented
    # pool is one contiguous span [head_rank, head[j]) merged at a single
    # finish, so formation recovers it as span work - pool_w.
    pool_code: jnp.ndarray    # [H] packed (head rank, fragmented, count)
    grp_jtype: jnp.ndarray    # [ring] type of each running group
    # per-slot requeue stash, resolved by the credit walk at finish:
    #   grp_rem_cnt > 0 — walk path: 1 + qlo * (N+1) + tail span code,
    #     grp_rem_w = credit available (pool residual + chaos credit)
    #   grp_rem_cnt < 0 — fragmented-pool fallback: -count,
    #     grp_rem_w / grp_rem_oldest = the PR-5 aggregate remainder
    #   grp_rem_cnt == 0 — nothing to requeue
    grp_rem_w: jnp.ndarray    # [ring] available credit / aggregate work
    grp_rem_cnt: jnp.ndarray  # [ring] span code / negated count (see above)
    grp_rem_oldest: jnp.ndarray  # [ring] aggregate oldest (frag path only)
    lost_work: jnp.ndarray    # chip-seconds lost past checkpoints
    failures: jnp.ndarray
    straggler_kills: jnp.ndarray
    requeues: jnp.ndarray     # also the injection gate (vs max_requeues)
    requeued_jobs: jnp.ndarray  # members re-entering the queue, total


class DesResult(NamedTuple):
    start_t: jnp.ndarray
    run_start_t: jnp.ndarray
    qlen_int: jnp.ndarray
    busy_ns: jnp.ndarray
    useful_ns: jnp.ndarray
    n_groups: jnp.ndarray
    makespan: jnp.ndarray
    ok: jnp.ndarray           # simulation drained within the iteration cap
    budget_exhausted: jnp.ndarray  # iteration/step budget hit: truncated run
    lost_work: jnp.ndarray    # chip-seconds lost to failures (not clipped)
    failures: jnp.ndarray
    straggler_kills: jnp.ndarray
    requeues: jnp.ndarray     # requeue batches (one per failed/killed group)
    requeued_jobs: jnp.ndarray  # individual members re-entering the queue


def _window_overlap(a, b, t_end):
    """Length of [a, b] clipped to the metric window [0, t_end]."""
    return jnp.maximum(jnp.minimum(b, t_end) - jnp.minimum(a, t_end), 0.0)


def _credit_cut(tj_prefw, j, lo, hi, target):
    """Largest rank in [lo, hi] with ``tj_prefw[j, rank] <= target``.

    Equivalent to ``clip(searchsorted(tj_prefw[j], target, 'right') - 1,
    lo, hi)`` under the caller's invariant ``tj_prefw[j, lo] <= target``
    (prefix rows are non-decreasing, and target = prefw[lo] + nonneg),
    but as a fixed-trip branchless binary search: ceil(log2(N + 1))
    scalar gathers per event instead of materializing the [N + 1] row
    every scan step — the row gather alone pushed the fused chaos sweep
    to ~3x a zero-chaos lane, past the 2x CI bar.
    """
    steps = max(int(tj_prefw.shape[1] - 1).bit_length(), 1)
    for _ in range(steps):
        mid = (lo + hi + 1) >> 1
        go = tj_prefw[j, mid] <= target
        lo = jnp.where(go, mid, lo)
        hi = jnp.where(go, hi, mid - 1)
    return lo


def _resolve_remnant(pw: PackedWorkload, j_f, code, stored_w, stored_old,
                     dtype):
    """Resolve a ring slot's requeue stash at group finish.

    Returns ``(cnt, w, oldest, lo, hi, walk)`` — the remnant member set
    to merge into the type's pool. Walk path (``code > 0``): decode the
    span, run ClusterSim's in-order credit walk via `_credit_cut`, and
    derive count / work / oldest from the static work prefix sums, so
    the scan carries no per-slot member state beyond the (code, credit,
    oldest) triple. ``w`` excludes the partially-credited head member's
    residual, which formation recovers from the span aggregates (see
    `pool_code` in DesState). Frag path (``code < 0``) passes the stored
    aggregates through; ``code == 0`` resolves to an empty remnant
    (cnt 0, w 0, oldest +inf — identity under the pool merge).
    """
    N = pw.n_jobs
    zero_f = jnp.zeros((), dtype)
    eps = jnp.asarray(CREDIT_EPS, dtype)
    walk = code > 0
    span = jnp.maximum(code - 1, 0)
    qlo = (span // (N + 1)).astype(jnp.int32)
    hi = (span % (N + 1)).astype(jnp.int32)
    qlo_w = pw.tj_prefw[j_f, qlo]
    hi_w = pw.tj_prefw[j_f, hi]
    target = qlo_w + stored_w + eps
    cut = _credit_cut(pw.tj_prefw, j_f, qlo, hi, target)
    cut_w = pw.tj_prefw[j_f, cut]
    m_res = jnp.maximum(stored_w - (cut_w - qlo_w), zero_f)
    m_w = jnp.maximum(hi_w - cut_w - m_res, zero_f)
    m_cnt = hi - cut
    m_old = pw.tj_submit[j_f, jnp.minimum(cut, N - 1)]
    return (jnp.where(walk, m_cnt, -code),
            jnp.where(walk, m_w, stored_w),
            jnp.where(walk & (m_cnt > 0), m_old, stored_old),
            jnp.where(walk, cut, jnp.zeros((), jnp.int32)),
            hi,
            walk)


def _pool_decode(code, n_jobs):
    """(count, head rank, fragmented) from a packed `pool_code` value."""
    cnt = code % (n_jobs + 1)
    meta = code // (n_jobs + 1)
    return cnt, meta >> 1, (meta & 1) == 1


def _reconstruct_job_times(pw: PackedWorkload, log_key, log_t, log_m,
                           log_headw, s_j):
    """Vectorized post-pass: job -> its group via per-type searchsorted.

    Within a type, group tails strictly increase and partition that type's
    ranks, so job (j, r) belongs to the type-j group with the smallest
    tail > r. Encoding groups as `j * (N+1) + tail` and jobs as
    `j * (N+1) + rank` makes that one global sorted lookup: tails are in
    1..N so type blocks never interleave. The log may have any capacity
    L >= 1 (the while engine uses L = N, the scan engine L = its step
    budget); unused slots carry the int32-max pad key and sort last. Jobs
    never grouped (only possible when the iteration/budget cap was hit)
    keep start = +inf, which also keeps the `ok` flag's all-finite check
    faithful.
    """
    N = pw.n_jobs
    L = log_key.shape[0]
    dtype = pw.submit.dtype
    order = jnp.argsort(log_key)
    skey = log_key[order]
    q = pw.jtype * (N + 1) + pw.rank
    ppos = jnp.searchsorted(skey, q, side="right")
    g = order[jnp.minimum(ppos, L - 1)]
    covered = (ppos < L) & (log_key[g] // (N + 1) == pw.jtype)
    t0 = log_t[g]
    m_g = jnp.maximum(log_m[g], 1).astype(dtype)
    start_t = jnp.where(covered, t0, INF)
    run_start = t0 + s_j[pw.jtype] + (pw.cumw - log_headw[g]) / m_g
    run_start_t = jnp.where(covered, run_start, INF)
    return start_t, run_start_t


def simulate_packet(pw: PackedWorkload, k, s_init, m_nodes,
                    priority=None, t_max=None, max_iters: int | None = None,
                    ring: int | None = None,
                    chaos: ChaosConfig | None = None) -> DesResult:
    """Run the Packet algorithm DES (group-log event loop).

    Args:
      pw:      PackedWorkload (static shapes; close over for jit).
      k:       scale ratio (traced scalar — vmap axis of the sweep).
      s_init:  constant initialization time (traced scalar; per paper §6 the
               init time is one constant per experiment). Per-type init is
               s_j = s_init for all j.
      m_nodes: cluster size M (traced scalar int).
      priority, t_max: optional [H] job-type priorities / wait normalizers.
      ring:    running-group buffer size; default `resolve_ring(m_nodes, N)`.
      chaos:   optional ChaosConfig (module docstring "Chaos"). None traces
               the exact pre-chaos graph; the log capacity and iteration
               cap grow with the static requeue budget when set.
    """
    H, N = pw.n_types, pw.n_jobs
    ring = resolve_ring(m_nodes, N, ring)
    R = resolve_max_requeues(chaos, N)
    L = N + R                       # group-log capacity: G <= N + requeues
    dtype = precision.canonical_dtype(pw.submit.dtype)
    k = jnp.asarray(k, dtype)
    s_init = jnp.asarray(s_init, dtype)
    m_nodes = jnp.asarray(m_nodes, jnp.int32)
    s_j = jnp.full((H,), s_init, dtype)
    p_j = jnp.ones((H,), dtype) if priority is None else jnp.asarray(priority, dtype)
    tmax_j = (jnp.full((H,), 3600.0, dtype) if t_max is None
              else jnp.asarray(t_max, dtype))
    if max_iters is None:
        max_iters = 4 * N + 64 + 2 * R

    t_end_metric = pw.t_last_submit
    type_ids = jnp.arange(H)
    key_pad = jnp.iinfo(jnp.int32).max     # unused log slots sort last
    zero_f = jnp.zeros((), dtype)
    zero_i = jnp.zeros((), jnp.int32)
    one_i = jnp.ones((), jnp.int32)
    u_all = None if chaos is None else chaos_uniforms(chaos, dtype, L)

    def sched_cond(carry):
        st = carry
        nonempty = st.tail > st.head
        if chaos is not None:
            nonempty = nonempty | (st.pool_code > 0)
        free_slot = jnp.any(jnp.isinf(st.grp_end))
        return (st.m_free > 0) & jnp.any(nonempty) & free_slot

    def sched_body(st: DesState) -> DesState:
        nonempty = st.tail > st.head
        sum_w = (pw.tj_prefw[type_ids, st.tail] -
                 pw.tj_prefw[type_ids, st.head])
        oldest = pw.tj_submit[type_ids, jnp.minimum(st.head, N - 1)]
        if chaos is not None:
            # requeued remainder counts toward weight / age / emptiness
            nonempty = nonempty | (st.pool_code > 0)
            sum_w = sum_w + st.pool_w
            oldest = jnp.minimum(oldest, st.pool_oldest)
        w = packet.queue_weights(sum_w, s_j, p_j, oldest, st.t, tmax_j, nonempty)
        # argmax index dtype follows x64 state; pin int32 so the log key
        # scatter below stays exact under the float64 opt-in.
        j = jnp.argmax(w).astype(jnp.int32)                   # Step 2
        work = sum_w[j]
        m_grp = packet.group_nodes(work, k, s_j[j], st.m_free)  # Step 4
        dur = packet.group_duration(work, s_j[j], m_grp)
        slot = jnp.argmax(jnp.isinf(st.grp_end))

        # O(1) group-log append; job times reconstructed after the loop
        gslot = jnp.minimum(st.n_groups, L - 1)
        head_w = pw.tj_prefw[j, st.head[j]]

        upd = {}
        if chaos is None:
            t_fin = st.t + dur
            useful_end = t_fin
        else:
            out = _chaos_outcome(chaos, u_all[gslot, 0], u_all[gslot, 1],
                                 st.requeues < R, s_j[j], work, m_grp, dur,
                                 dtype)
            t_fin = st.t + out.dur
            useful_end = jnp.where(out.failed,
                                   st.t + s_j[j] + out.ckpt_done, t_fin)
            requeued = out.failed | out.killed
            # Stash the requeue for the group's finish event. The drained
            # queue is the rank span [qlo, tail) of type j with a possible
            # done-work residual on its head member; the per-member credit
            # walk (ClusterSim _requeue, oldest first) is DEFERRED to the
            # finish (_resolve_remnant), so the ring carries only a span
            # code and the available credit — no extra per-slot arrays.
            eps = jnp.asarray(CREDIT_EPS, dtype)
            p_cnt, p_lo, p_frag = _pool_decode(st.pool_code[j], N)
            has_pool = p_cnt > 0
            qlo = jnp.where(has_pool, p_lo, st.head[j])
            # recover the head member's done-work residual from the span
            # aggregates (non-fragmented pool = one contiguous span
            # [qlo, head) merged at a single finish)
            res0 = jnp.where(has_pool, jnp.maximum(
                head_w - pw.tj_prefw[j, qlo] - st.pool_w[j], zero_f),
                zero_f)
            walk_ok = ~(has_pool & p_frag)
            avail = res0 + out.credit
            # span code 1 + qlo*(N+1) + tail stays well inside int32 for
            # the paper's N <= 5000 (bound ~ (N+1)^2)
            span_code = 1 + qlo * (N + 1) + st.tail[j]
            # fragmented pool: PR-5 aggregate upper bound for this batch
            rem_agg = work - out.credit
            a_has = requeued & (rem_agg > eps)
            a_cnt = (st.tail[j] - st.head[j]) + p_cnt
            code = jnp.where(requeued & walk_ok, span_code,
                             jnp.where(a_has, -a_cnt, zero_i))
            stash_w = jnp.where(
                requeued & walk_ok, avail,
                jnp.where(a_has, jnp.maximum(rem_agg, zero_f), zero_f))
            stash_old = jnp.where(a_has & ~walk_ok, oldest[j], INF)
            upd = dict(
                grp_jtype=st.grp_jtype.at[slot].set(j),
                grp_rem_w=st.grp_rem_w.at[slot].set(stash_w),
                grp_rem_cnt=st.grp_rem_cnt.at[slot].set(code),
                grp_rem_oldest=st.grp_rem_oldest.at[slot].set(stash_old),
                pool_w=st.pool_w.at[j].set(zero_f),
                pool_oldest=st.pool_oldest.at[j].set(INF),
                pool_code=st.pool_code.at[j].set(zero_i),
                lost_work=st.lost_work + out.lost,
                failures=st.failures + jnp.where(out.failed, one_i, zero_i),
                straggler_kills=st.straggler_kills + jnp.where(
                    out.killed & ~out.failed, one_i, zero_i),
                requeues=st.requeues + jnp.where(requeued, one_i, zero_i))

        busy_inc = m_grp.astype(dtype) * _window_overlap(
            st.t, t_fin, t_end_metric)
        useful_inc = m_grp.astype(dtype) * _window_overlap(
            st.t + s_j[j], useful_end, t_end_metric)
        if chaos is not None:
            # discourage fused mul-add rounding so the scan engine's
            # separately-rounded accumulates usually match bit for bit
            # (best effort in float32 — see sweep._packet_one; exact in
            # float64, which is what tests assert bitwise cross-engine)
            busy_inc, useful_inc = jax.lax.optimization_barrier(
                (busy_inc, useful_inc))
        busy = st.busy_ns + busy_inc
        useful = st.useful_ns + useful_inc

        return st._replace(
            head=st.head.at[j].set(st.tail[j]),               # Step 3: drain all
            m_free=st.m_free - m_grp,
            grp_end=st.grp_end.at[slot].set(t_fin),
            grp_m=st.grp_m.at[slot].set(m_grp),
            log_key=st.log_key.at[gslot].set(j * (N + 1) + st.tail[j]),
            log_t=st.log_t.at[gslot].set(st.t),
            log_m=st.log_m.at[gslot].set(m_grp),
            log_headw=st.log_headw.at[gslot].set(head_w),
            busy_ns=busy, useful_ns=useful,
            n_groups=st.n_groups + 1, **upd)

    def cond(st: DesState):
        more = (st.next_sub < N) | jnp.any(~jnp.isinf(st.grp_end))
        return more & (st.iters < max_iters)

    def body(st: DesState) -> DesState:
        t_sub = jnp.where(st.next_sub < N,
                          pw.submit[jnp.minimum(st.next_sub, N - 1)], INF)
        slot = jnp.argmin(st.grp_end)
        t_fin = st.grp_end[slot]
        take_sub = t_sub <= t_fin
        t_new = jnp.where(take_sub, t_sub, t_fin)

        # queue-length integral over the elapsed interval (clipped to window)
        qlen = jnp.sum(st.tail - st.head).astype(st.t.dtype)
        q_inc = qlen * _window_overlap(st.t, t_new, t_end_metric)
        if chaos is not None:
            qlen = qlen + jnp.sum(st.pool_code % (N + 1)).astype(st.t.dtype)
            q_inc = jax.lax.optimization_barrier(
                qlen * _window_overlap(st.t, t_new, t_end_metric))
        qint = st.qlen_int + q_inc

        def on_submit(st):
            j = pw.jtype[jnp.minimum(st.next_sub, N - 1)]
            return st._replace(next_sub=st.next_sub + 1,
                               tail=st.tail.at[j].add(1))

        def on_finish(st):
            upd = {}
            if chaos is not None:
                # resolve the stashed requeue into its member set NOW —
                # the queue must not see it before the group's end, and
                # ClusterSim's _requeue credits members at the same time
                j_f = st.grp_jtype[slot]
                cnt, rem_w, rem_old, rem_lo, rem_hi, walk = (
                    _resolve_remnant(pw, j_f, st.grp_rem_cnt[slot],
                                     st.grp_rem_w[slot],
                                     st.grp_rem_oldest[slot], dtype))
                old_cnt, old_lo, old_frag = _pool_decode(
                    st.pool_code[j_f], N)
                inc = cnt > 0
                was_empty = old_cnt == 0
                # the remnant span abuts the live window only if no
                # formation of this type ran while the group held it
                contig = rem_hi == st.head[j_f]
                frag = jnp.where(
                    inc, old_frag | ~walk | ~was_empty | ~contig, old_frag)
                new_lo = jnp.where(was_empty, rem_lo,
                                   jnp.minimum(old_lo, rem_lo))
                new_code = ((new_lo * 2 + frag.astype(jnp.int32))
                            * (N + 1) + old_cnt + cnt)
                upd = dict(
                    pool_w=st.pool_w.at[j_f].add(rem_w),
                    pool_oldest=st.pool_oldest.at[j_f].min(rem_old),
                    pool_code=st.pool_code.at[j_f].set(jnp.where(
                        inc, new_code, st.pool_code[j_f])),
                    grp_rem_w=st.grp_rem_w.at[slot].set(zero_f),
                    grp_rem_cnt=st.grp_rem_cnt.at[slot].set(zero_i),
                    grp_rem_oldest=st.grp_rem_oldest.at[slot].set(INF),
                    requeued_jobs=st.requeued_jobs + cnt)
            return st._replace(m_free=st.m_free + st.grp_m[slot],
                               grp_end=st.grp_end.at[slot].set(INF),
                               grp_m=st.grp_m.at[slot].set(0), **upd)

        st = st._replace(t=t_new, qlen_int=qint)
        st = jax.lax.cond(take_sub, on_submit, on_finish, st)
        st = jax.lax.while_loop(sched_cond, sched_body, st)   # Steps 1-5
        return st._replace(iters=st.iters + 1)

    st0 = DesState(
        t=jnp.zeros((), dtype), next_sub=jnp.zeros((), jnp.int32),
        head=jnp.zeros((H,), jnp.int32), tail=jnp.zeros((H,), jnp.int32),
        m_free=m_nodes, grp_end=jnp.full((ring,), INF, dtype),
        grp_m=jnp.zeros((ring,), jnp.int32),
        log_key=jnp.full((L,), key_pad, jnp.int32),
        log_t=jnp.zeros((L,), dtype), log_m=jnp.zeros((L,), jnp.int32),
        log_headw=jnp.zeros((L,), dtype),
        qlen_int=jnp.zeros((), dtype), busy_ns=jnp.zeros((), dtype),
        useful_ns=jnp.zeros((), dtype), n_groups=jnp.zeros((), jnp.int32),
        iters=jnp.zeros((), jnp.int32),
        pool_w=jnp.zeros((H,), dtype),
        pool_oldest=jnp.full((H,), INF, dtype),
        pool_code=jnp.zeros((H,), jnp.int32),
        grp_jtype=jnp.zeros((ring,), jnp.int32),
        grp_rem_w=jnp.zeros((ring,), dtype),
        grp_rem_cnt=jnp.zeros((ring,), jnp.int32),
        grp_rem_oldest=jnp.full((ring,), INF, dtype),
        lost_work=jnp.zeros((), dtype), failures=jnp.zeros((), jnp.int32),
        straggler_kills=jnp.zeros((), jnp.int32),
        requeues=jnp.zeros((), jnp.int32),
        requeued_jobs=jnp.zeros((), jnp.int32))

    st = jax.lax.while_loop(cond, body, st0)
    start_t, run_start_t = _reconstruct_job_times(
        pw, st.log_key, st.log_t, st.log_m, st.log_headw, s_j)
    drained = (st.next_sub >= N) & jnp.all(jnp.isinf(st.grp_end)) & \
        jnp.all(st.head == st.tail)
    if chaos is not None:
        drained = drained & jnp.all(st.pool_code == 0)
    ok = drained & jnp.all(jnp.isfinite(start_t))
    return DesResult(start_t=start_t, run_start_t=run_start_t,
                     qlen_int=st.qlen_int, busy_ns=st.busy_ns,
                     useful_ns=st.useful_ns, n_groups=st.n_groups,
                     makespan=st.t, ok=ok, budget_exhausted=~drained,
                     lost_work=st.lost_work, failures=st.failures,
                     straggler_kills=st.straggler_kills,
                     requeues=st.requeues, requeued_jobs=st.requeued_jobs)


# --------------------------------------------------------------------------
# Event-budget scan engine: the batched-lane form of the group-log DES.
# --------------------------------------------------------------------------

EVENT_BUDGET_SLACK = 64   # headroom over the 3N analytic step bound
SCAN_SEG = 256            # default segment length (early-exit granularity)


def event_budget(n_jobs: int, max_requeues: int = 0) -> int:
    """Safe per-grid step budget for `simulate_packet_scan`.

    Each scan step either consumes one event (a submission or a group
    completion: at most N + G of those) or forms one group (G of those),
    and every group drains >= 1 job OR the pool content of one prior
    requeue, so G <= N + R where R is the bounded requeue-injection count
    (`ChaosConfig.max_requeues`; 0 without chaos). 3N + 2R + slack steps
    therefore always drain a lane, whatever its (k, s) and fault draws.
    """
    return 3 * max(1, int(n_jobs)) + 2 * max(0, int(max_requeues)) + \
        EVENT_BUDGET_SLACK


class _ScanState(NamedTuple):
    t: jnp.ndarray            # current time
    next_sub: jnp.ndarray     # index of next submission (global order)
    head: jnp.ndarray         # [H] per-type queue window start (rank)
    tail: jnp.ndarray         # [H] per-type queue window end (rank)
    m_free: jnp.ndarray       # free nodes
    grp_end: jnp.ndarray      # [ring] completion time of running groups
    grp_m: jnp.ndarray        # [ring] nodes held
    qlen_int: jnp.ndarray
    busy_ns: jnp.ndarray
    useful_ns: jnp.ndarray
    n_groups: jnp.ndarray
    # chaos state (zeros / untouched when chaos is None)
    pool_w: jnp.ndarray       # [H] requeued remainder work per type
    pool_oldest: jnp.ndarray  # [H] oldest submit among requeued jobs
    pool_code: jnp.ndarray    # [H] packed span/frag/count (DesState)
    grp_jtype: jnp.ndarray    # [ring]
    grp_rem_w: jnp.ndarray    # [ring] available credit / aggregate work
    grp_rem_cnt: jnp.ndarray  # [ring] span code / negated count (DesState)
    grp_rem_oldest: jnp.ndarray  # [ring] aggregate oldest (frag path only)
    lost_work: jnp.ndarray
    failures: jnp.ndarray
    straggler_kills: jnp.ndarray
    requeues: jnp.ndarray
    requeued_jobs: jnp.ndarray


#: the recognized per-event step implementations of the scan engine
STEP_IMPLS = ("xla", "pallas")


def _check_step_impl(step_impl: str) -> str:
    if step_impl not in STEP_IMPLS:
        raise ValueError(f"unknown step_impl {step_impl!r}; "
                         f"available: {STEP_IMPLS}")
    return step_impl


def _first(x, reduce):
    """Index of the first `reduce`-extreme along axis 0, by iota compare:
    `jnp.argmax` (reduce=jnp.max) or `jnp.argmin` (jnp.min). A 1-D row
    (one vmapped lane) gives a scalar; an [n, T] block of lane columns
    (the Pallas kernel's layout) gives a [1, T] row."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    hit = x == reduce(x, axis=0, keepdims=True)
    return jnp.min(jnp.where(hit, rows, x.shape[0]), axis=0,
                   keepdims=x.ndim > 1)


def _rows(n, idx):
    """Mask of row `idx` along an axis of size n: [n] for a scalar index,
    [n, T] for a [1, T] row of indices."""
    shape = (n,) + jnp.shape(idx)[1:]
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) == idx


def _pick(x, sel):
    """The element of `x` along axis 0 where `sel` holds (exactly one), as
    a masked max over a -inf / int-min floor, so it comes back unchanged
    (signed zeros and infinities included; a subnormal float flushes to
    zero, as through any float op under XLA). Shapes as `_first`."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        low = jnp.asarray(-jnp.inf, x.dtype)
    else:
        low = jnp.asarray(jnp.iinfo(x.dtype).min, x.dtype)
    return jnp.max(jnp.where(sel, x, low), axis=0, keepdims=x.ndim > 1)


def packet_scan_step(pw: PackedWorkload, k, s_j, p_j, tmax_j,
                     st: _ScanState, *, r_cap: int = 0, chaos=None,
                     u_all=None):
    """ONE fused event step of the scan engine — the canonical semantics.

    Branchlessly either forms one group (greedy pass unblocked) or consumes
    one event (submission / group finish), with every state write masked by
    `do_sched` / `do_event`. This module-level form is shared by BOTH step
    implementations of `simulate_packet_scan`: the XLA engine scans it
    directly, and `repro.kernels.packet_step` re-exports it as the pure-jnp
    reference (`ref.py`) that the lane-batched Pallas kernel body mirrors —
    one source of truth for the event arithmetic, so the engines cannot
    drift apart silently.

    Every read and write along the small state axes (types H, ring slots)
    is an iota-compare select (`_first`, `_pick`, masked `where` on
    `_rows`), the kernel body's own form: exact, and under `vmap` free of
    the batched gathers and scatters that cost per-index work on the TPU.
    Only the job-axis tables of `pw` and the chaos stream `u_all` are
    gathered.

    Args mirror `simulate_packet_scan`'s internals: `s_j`/`p_j`/`tmax_j`
    are the [H] per-type init/priority/wait-normalizer rows, `r_cap` the
    static requeue-injection budget R, and `u_all` the [N + R, 2] per-lane
    uniform stream (required iff `chaos` is given). Returns
    ``(new_state, (log_key, log_t, log_m, log_headw))``.
    """
    H, N = pw.n_types, pw.n_jobs
    dtype = st.t.dtype
    t_end_metric = pw.t_last_submit
    type_ids = jnp.arange(H)
    key_pad = jnp.iinfo(jnp.int32).max
    zero_f = jnp.zeros((), dtype)
    zero_i = jnp.zeros((), jnp.int32)
    one_i = jnp.ones((), jnp.int32)
    R = r_cap

    nonempty = st.tail > st.head
    if chaos is not None:
        nonempty = nonempty | (st.pool_code > 0)
    free_mask = jnp.isinf(st.grp_end)
    queued = jnp.any(nonempty)
    active = ((st.next_sub < N) | jnp.any(~jnp.isinf(st.grp_end)) |
              jnp.any(st.tail > st.head))
    if chaos is not None:
        active = active | jnp.any(st.pool_code > 0)
    can_sched = (st.m_free > 0) & queued & jnp.any(free_mask)
    do_sched = active & can_sched
    do_event = active & ~can_sched

    # greedy scheduling pass (paper Steps 1-5), masked unless do_sched
    sum_w = (pw.tj_prefw[type_ids, st.tail] -
             pw.tj_prefw[type_ids, st.head])
    oldest = pw.tj_submit[type_ids, jnp.minimum(st.head, N - 1)]
    if chaos is not None:
        sum_w = sum_w + st.pool_w
        oldest = jnp.minimum(oldest, st.pool_oldest)
    w = packet.queue_weights(sum_w, s_j, p_j, oldest, st.t, tmax_j,
                             nonempty)
    j = _first(w, jnp.max)
    at_j = _rows(H, j)
    work = _pick(sum_w, at_j)
    s_grp = _pick(s_j, at_j)
    tail_j = _pick(st.tail, at_j)
    head_j = _pick(st.head, at_j)
    m_grp = packet.group_nodes(work, k, s_grp, st.m_free)
    dur = packet.group_duration(work, s_grp, m_grp)
    at_s = _rows(free_mask.shape[0],
                 _first(free_mask.astype(jnp.int32), jnp.max))
    head_w = pw.tj_prefw[j, head_j]
    if chaos is None:
        t_gfin = st.t + dur
        useful_end = t_gfin
    else:
        L_cap = u_all.shape[0]
        gslot = jnp.minimum(st.n_groups, L_cap - 1)
        out = _chaos_outcome(chaos, u_all[gslot, 0], u_all[gslot, 1],
                             st.requeues < R, s_grp, work, m_grp, dur,
                             dtype)
        t_gfin = st.t + out.dur
        useful_end = jnp.where(out.failed,
                               st.t + s_grp + out.ckpt_done, t_gfin)
        requeued = do_sched & (out.failed | out.killed)
        # stash the requeue span + credit for the finish event — see
        # simulate_packet for the deferred-walk notes
        eps = jnp.asarray(CREDIT_EPS, dtype)
        p_cnt, p_lo, p_frag = _pool_decode(_pick(st.pool_code, at_j), N)
        has_pool = p_cnt > 0
        qlo = jnp.where(has_pool, p_lo, head_j)
        res0 = jnp.where(has_pool, jnp.maximum(
            head_w - pw.tj_prefw[j, qlo] - _pick(st.pool_w, at_j), zero_f),
            zero_f)
        walk_ok = ~(has_pool & p_frag)
        avail = res0 + out.credit
        span_code = 1 + qlo * (N + 1) + tail_j
        rem_agg = work - out.credit
        a_has = requeued & (rem_agg > eps)
        a_cnt = (tail_j - head_j) + p_cnt
        code = jnp.where(requeued & walk_ok, span_code,
                         jnp.where(a_has, -a_cnt, zero_i))
        stash_w = jnp.where(
            requeued & walk_ok, avail,
            jnp.where(a_has, jnp.maximum(rem_agg, zero_f), zero_f))
        stash_old = jnp.where(a_has & ~walk_ok, _pick(oldest, at_j), INF)
    busy_inc = m_grp.astype(dtype) * _window_overlap(
        st.t, t_gfin, t_end_metric)
    useful_inc = m_grp.astype(dtype) * _window_overlap(
        st.t + s_grp, useful_end, t_end_metric)
    if chaos is not None:
        # same best-effort rounding contract as the while engine
        busy_inc, useful_inc = jax.lax.optimization_barrier(
            (busy_inc, useful_inc))

    # event step (submission or completion), masked unless do_event
    t_sub = jnp.where(st.next_sub < N,
                      pw.submit[jnp.minimum(st.next_sub, N - 1)], INF)
    at_e = _rows(st.grp_end.shape[0], _first(st.grp_end, jnp.min))
    t_efin = jnp.min(st.grp_end)        # the element at the first argmin
    take_sub = t_sub <= t_efin
    t_new = jnp.where(take_sub, t_sub, t_efin)
    qlen = jnp.sum(st.tail - st.head).astype(dtype)
    if chaos is not None:
        qlen = qlen + jnp.sum(st.pool_code % (N + 1)).astype(dtype)
    q_inc = qlen * _window_overlap(st.t, t_new, t_end_metric)
    if chaos is not None:
        q_inc = jax.lax.optimization_barrier(q_inc)
    sub_j = pw.jtype[jnp.minimum(st.next_sub, N - 1)]

    do_submit = do_event & take_sub
    do_finish = do_event & ~take_sub
    set_j = at_j & do_sched
    set_s = at_s & do_sched
    clr_e = at_e & do_finish

    head = jnp.where(set_j, st.tail, st.head)
    tail = st.tail + jnp.where(_rows(H, sub_j) & do_submit, one_i, zero_i)
    m_free = (st.m_free - jnp.where(do_sched, m_grp, zero_i)
              + jnp.where(do_finish, _pick(st.grp_m, at_e), zero_i))
    grp_end = jnp.where(clr_e, INF, jnp.where(set_s, t_gfin, st.grp_end))
    grp_m = jnp.where(clr_e, zero_i, jnp.where(set_s, m_grp, st.grp_m))

    y = (jnp.where(do_sched, j * (N + 1) + tail_j, key_pad),
         jnp.where(do_sched, st.t, zero_f),
         jnp.where(do_sched, m_grp, zero_i),
         jnp.where(do_sched, head_w, zero_f))

    if chaos is None:
        chaos_upd = {}
    else:
        # formation clears the drained pool and stashes the requeue in
        # the ring; the finish event resolves the stash into its member
        # set (_resolve_remnant) and releases it back to the pool
        j_f = _pick(st.grp_jtype, at_e)
        at_f = _rows(H, j_f)
        cnt_r, rem_w_r, rem_old_r, rem_lo_r, rem_hi_r, walk_r = (
            _resolve_remnant(pw, j_f, _pick(st.grp_rem_cnt, at_e),
                             _pick(st.grp_rem_w, at_e),
                             _pick(st.grp_rem_oldest, at_e), dtype))
        old_cnt, old_lo, old_frag = _pool_decode(
            _pick(st.pool_code, at_f), N)
        inc = do_finish & (cnt_r > 0)
        was_empty = old_cnt == 0
        contig = rem_hi_r == _pick(st.head, at_f)
        frag = jnp.where(
            inc, old_frag | ~walk_r | ~was_empty | ~contig, old_frag)
        new_lo = jnp.where(was_empty, rem_lo_r,
                           jnp.minimum(old_lo, rem_lo_r))
        new_code = ((new_lo * 2 + frag.astype(jnp.int32))
                    * (N + 1) + old_cnt + cnt_r)
        pool_w = jnp.where(set_j, zero_f, st.pool_w)
        pool_oldest = jnp.where(set_j, INF, st.pool_oldest)
        pool_code = jnp.where(set_j, zero_i, st.pool_code)
        chaos_upd = dict(
            pool_w=jnp.where(at_f, pool_w + jnp.where(
                do_finish, rem_w_r, zero_f), pool_w),
            pool_oldest=jnp.where(at_f, jnp.minimum(pool_oldest, jnp.where(
                do_finish, rem_old_r, INF)), pool_oldest),
            pool_code=jnp.where(at_f & inc, new_code, pool_code),
            grp_jtype=jnp.where(set_s, j, st.grp_jtype),
            grp_rem_w=jnp.where(clr_e, zero_f,
                                jnp.where(set_s, stash_w, st.grp_rem_w)),
            grp_rem_cnt=jnp.where(clr_e, zero_i,
                                  jnp.where(set_s, code, st.grp_rem_cnt)),
            grp_rem_oldest=jnp.where(clr_e, INF, jnp.where(
                set_s, stash_old, st.grp_rem_oldest)),
            lost_work=st.lost_work + jnp.where(do_sched, out.lost,
                                               zero_f),
            failures=st.failures + jnp.where(do_sched & out.failed,
                                             one_i, zero_i),
            straggler_kills=st.straggler_kills + jnp.where(
                do_sched & out.killed & ~out.failed, one_i, zero_i),
            requeues=st.requeues + jnp.where(requeued, one_i, zero_i),
            requeued_jobs=st.requeued_jobs + jnp.where(
                do_finish, cnt_r, zero_i))

    st = st._replace(
        t=jnp.where(do_event, t_new, st.t),
        next_sub=st.next_sub + jnp.where(do_submit, one_i, zero_i),
        head=head, tail=tail, m_free=m_free,
        grp_end=grp_end, grp_m=grp_m,
        qlen_int=st.qlen_int + jnp.where(do_event, q_inc, zero_f),
        busy_ns=st.busy_ns + jnp.where(do_sched, busy_inc, zero_f),
        useful_ns=st.useful_ns + jnp.where(do_sched, useful_inc, zero_f),
        n_groups=st.n_groups + jnp.where(do_sched, one_i, zero_i),
        **chaos_upd)
    return st, y


def simulate_packet_scan(pw: PackedWorkload, k, s_init, m_nodes,
                         priority=None, t_max=None, ring: int | None = None,
                         budget: int | None = None,
                         seg: int | None = None,
                         chaos: ChaosConfig | None = None,
                         step_impl: str = "xla",
                         with_segments: bool = False):
    """Packet DES as a fixed-budget `lax.scan` — the batched-lane engine.

    Same policy and same per-step arithmetic as `simulate_packet`, but
    restructured for vmapping over many (k, s) lanes at once:

      * ONE flat step kind instead of an outer event loop with a nested
        scheduling `while_loop`: each step either forms one group (when the
        greedy pass is unblocked) or consumes one event, chosen branchlessly
        with masks, so vmapped lanes never pay a both-branches `lax.cond`
        or a lockstep inner loop.
      * the group log is EMITTED as scan outputs (`ys`) instead of carried
        as [N] state and scattered per step — under vmap the while engine
        drags [lanes, N] log arrays through every iteration, which is the
        dominant cost of the old fused mode on CPU.
      * a drained lane carries `active = False` and its step is a no-op
        (masked updates, pad log key), so lanes of different event counts
        can share one program.
      * the scan runs in `seg`-length segments under a `while_loop` that
        stops as soon as every lane in the dispatch has drained ("event
        budget with early exit"): the budget is the analytic worst case
        (`event_budget(N)` ~ 3N), but a dispatch of short lanes pays only
        its own steps, rounded up to a segment.

    `pw` is an ordinary operand and batches like any other: vmapping with
    ``in_axes=(0, 0, 0, None, None)`` over a stacked PackedWorkload (see
    `repro.core.cohort`) runs W same-shape workloads in one program, which
    is how `run_cohort_grid` folds the paper's whole 6-workflow study into
    two dispatched cohorts. Extra budget segments past a lane's drain point
    are masked no-ops (active=False emits pad log keys and freezes state),
    so per-lane results are independent of whatever else shares the
    dispatch — the property every equivalence test in the suite leans on.

    Results are equivalent to `simulate_packet` lane-for-lane (the
    equivalence suite pins every DesResult field); `ok` is False only if
    the budget was insufficient, which the 3N bound rules out for the
    default.

    Engine selection (`step_impl`):

      * ``"xla"`` (default, and the only engine on CPU worth running
        compiled): the per-event step scans `packet_scan_step` directly
        and lanes batch via `vmap`. This stays the default everywhere —
        zero behaviour change for existing callers.
      * ``"pallas"``: the same event arithmetic as a lane-minor Pallas
        kernel (`repro.kernels.packet_step`) with the ring state in
        kernel memory across the select/commit chain, invoked once per
        event for a whole dispatch of lanes. On the TPU it compiles
        through Mosaic, float32 only (float64 raises
        `PallasUnsupportedError`); on CPU it runs in interpret mode
        (discharged back into XLA), so it is a correctness/parity path
        there, not a fast path. Schedules and integer counters are
        bitwise-identical to ``"xla"`` in both dtypes, chaos on and off
        (pinned in interpret mode by tests/test_packet_step.py); float
        time-integrals may differ in final ulps, same as every
        cross-engine contract in this module.

    A single (k, s) pair routed through ``"pallas"`` runs as a 1-lane
    dispatch of `simulate_packet_scan_lanes`; batch callers should use
    the lanes entry point directly.

    ``with_segments=True`` returns ``(DesResult, segments)``: the int32
    number of `seg`-long segments the lane ran before it drained (the
    while loop's own counter), so it ran ``segments * seg`` steps, masked
    or not. Under `vmap` the count is per lane, and the batched loop ran
    the largest of them for every lane.
    """
    _check_step_impl(step_impl)
    if step_impl == "pallas":
        res, segs = simulate_packet_scan_lanes(
            pw, jnp.asarray(k)[None], jnp.asarray(s_init)[None], m_nodes,
            priority=priority, t_max=t_max, ring=ring, budget=budget,
            seg=seg, chaos=chaos, step_impl="pallas", with_segments=True)
        res = jax.tree.map(lambda x: x[0], res)
        return (res, segs[0]) if with_segments else res
    H, N = pw.n_types, pw.n_jobs
    ring = resolve_ring(m_nodes, N, ring)
    R = resolve_max_requeues(chaos, N)
    L_cap = N + R               # formation cap == uniform-stream length
    budget = event_budget(N, R) if budget is None else max(1, int(budget))
    seg = SCAN_SEG if seg is None else max(1, int(seg))
    n_segs = -(-budget // seg)
    budget = n_segs * seg               # segments tile the log exactly
    dtype = precision.canonical_dtype(pw.submit.dtype)
    k = jnp.asarray(k, dtype)
    s_init = jnp.asarray(s_init, dtype)
    m_nodes = jnp.asarray(m_nodes, jnp.int32)
    s_j = jnp.full((H,), s_init, dtype)
    p_j = jnp.ones((H,), dtype) if priority is None else jnp.asarray(priority, dtype)
    tmax_j = (jnp.full((H,), 3600.0, dtype) if t_max is None
              else jnp.asarray(t_max, dtype))

    key_pad = jnp.iinfo(jnp.int32).max
    u_all = None if chaos is None else chaos_uniforms(chaos, dtype, L_cap)

    def lane_active(st: _ScanState):
        active = ((st.next_sub < N) | jnp.any(~jnp.isinf(st.grp_end)) |
                  jnp.any(st.tail > st.head))
        if chaos is not None:
            active = active | jnp.any(st.pool_code > 0)
        return active

    def step(st: _ScanState, _):
        return packet_scan_step(pw, k, s_j, p_j, tmax_j, st,
                                r_cap=R, chaos=chaos, u_all=u_all)

    def seg_cond(carry):
        st, _, s_idx = carry
        return lane_active(st) & (s_idx < n_segs)

    def seg_body(carry):
        st, logs, s_idx = carry
        st, ys = jax.lax.scan(step, st, None, length=seg)
        off = s_idx * seg
        logs = tuple(jax.lax.dynamic_update_slice(buf, y, (off,))
                     for buf, y in zip(logs, ys))
        return st, logs, s_idx + 1

    st0 = _ScanState(
        t=jnp.zeros((), dtype), next_sub=jnp.zeros((), jnp.int32),
        head=jnp.zeros((H,), jnp.int32), tail=jnp.zeros((H,), jnp.int32),
        m_free=m_nodes, grp_end=jnp.full((ring,), INF, dtype),
        grp_m=jnp.zeros((ring,), jnp.int32),
        qlen_int=jnp.zeros((), dtype), busy_ns=jnp.zeros((), dtype),
        useful_ns=jnp.zeros((), dtype), n_groups=jnp.zeros((), jnp.int32),
        pool_w=jnp.zeros((H,), dtype),
        pool_oldest=jnp.full((H,), INF, dtype),
        pool_code=jnp.zeros((H,), jnp.int32),
        grp_jtype=jnp.zeros((ring,), jnp.int32),
        grp_rem_w=jnp.zeros((ring,), dtype),
        grp_rem_cnt=jnp.zeros((ring,), jnp.int32),
        grp_rem_oldest=jnp.full((ring,), INF, dtype),
        lost_work=jnp.zeros((), dtype), failures=jnp.zeros((), jnp.int32),
        straggler_kills=jnp.zeros((), jnp.int32),
        requeues=jnp.zeros((), jnp.int32),
        requeued_jobs=jnp.zeros((), jnp.int32))
    logs0 = (jnp.full((budget,), key_pad, jnp.int32),
             jnp.zeros((budget,), dtype),
             jnp.zeros((budget,), jnp.int32),
             jnp.zeros((budget,), dtype))

    st, logs, n_run = jax.lax.while_loop(
        seg_cond, seg_body, (st0, logs0, jnp.zeros((), jnp.int32)))
    log_key, log_t, log_m, log_headw = logs
    start_t, run_start_t = _reconstruct_job_times(
        pw, log_key, log_t, log_m, log_headw, s_j)
    drained = (st.next_sub >= N) & jnp.all(jnp.isinf(st.grp_end)) & \
        jnp.all(st.head == st.tail)
    if chaos is not None:
        drained = drained & jnp.all(st.pool_code == 0)
    ok = drained & jnp.all(jnp.isfinite(start_t))
    res = DesResult(start_t=start_t, run_start_t=run_start_t,
                    qlen_int=st.qlen_int, busy_ns=st.busy_ns,
                    useful_ns=st.useful_ns, n_groups=st.n_groups,
                    makespan=st.t, ok=ok, budget_exhausted=~drained,
                    lost_work=st.lost_work, failures=st.failures,
                    straggler_kills=st.straggler_kills,
                    requeues=st.requeues, requeued_jobs=st.requeued_jobs)
    return (res, n_run) if with_segments else res


def _lane_cols_to_rows(cols: _ScanState) -> _ScanState:
    """Kernel layout [state, T] -> lane-major [T, state] for assembly."""
    return _ScanState(
        t=cols.t[0], next_sub=cols.next_sub[0],
        head=cols.head.T, tail=cols.tail.T, m_free=cols.m_free[0],
        grp_end=cols.grp_end.T, grp_m=cols.grp_m.T,
        qlen_int=cols.qlen_int[0], busy_ns=cols.busy_ns[0],
        useful_ns=cols.useful_ns[0], n_groups=cols.n_groups[0],
        pool_w=cols.pool_w.T, pool_oldest=cols.pool_oldest.T,
        pool_code=cols.pool_code.T, grp_jtype=cols.grp_jtype.T,
        grp_rem_w=cols.grp_rem_w.T, grp_rem_cnt=cols.grp_rem_cnt.T,
        grp_rem_oldest=cols.grp_rem_oldest.T,
        lost_work=cols.lost_work[0], failures=cols.failures[0],
        straggler_kills=cols.straggler_kills[0], requeues=cols.requeues[0],
        requeued_jobs=cols.requeued_jobs[0])


def simulate_packet_scan_lanes(pw: PackedWorkload, k, s_init, m_nodes,
                               priority=None, t_max=None,
                               ring: int | None = None,
                               budget: int | None = None,
                               seg: int | None = None,
                               chaos: ChaosConfig | None = None,
                               step_impl: str = "xla",
                               with_segments: bool = False):
    """A whole dispatch of (k, s) lanes through one scan engine.

    `k` and `s_init` are [T] lane arrays; `chaos` (optional) carries
    scalar or [T] leaves (broadcast here). Returns a DesResult whose
    every field has a leading lane axis — the same contract as vmapping
    `simulate_packet_scan`, which is exactly what ``step_impl="xla"``
    does.

    ``step_impl="pallas"`` instead keeps the lanes TOGETHER in one
    kernel invocation per event: state lives as [state, T] columns with
    lanes on the minor axis, and each scan step calls the fused
    `repro.kernels.packet_step` kernel, which advances every lane one
    event with the ring state in kernel memory (VMEM on TPU; interpret
    mode discharges it back into XLA on CPU). The event arithmetic is
    `packet_scan_step` vectorized over the lane axis — all per-lane
    reductions are argmax/argmin/any over the state axis and every
    float op is elementwise, so schedules and integer counters are
    bitwise-identical to the XLA path. Extra budget
    segments past a lane's drain point remain masked no-ops, so a
    lane's result is independent of its dispatch companions (the
    segmented early exit stops only when ALL lanes have drained).

    Call under `jax.jit` — the pallas path issues one kernel call per
    scan step and is built to be traced, not run op-by-op.

    ``with_segments=True`` returns ``(DesResult, segments)`` with [T]
    int32 segment counts, as `simulate_packet_scan` gives them per lane;
    the pallas path runs one loop for the whole dispatch, so every lane
    reads that loop's count.
    """
    _check_step_impl(step_impl)
    k = jnp.atleast_1d(k)
    s_init = jnp.atleast_1d(s_init)
    T = k.shape[0]
    if step_impl == "xla":
        run = partial(simulate_packet_scan, pw,
                      m_nodes=m_nodes, priority=priority,
                      t_max=t_max, ring=ring, budget=budget,
                      seg=seg, with_segments=with_segments)
        if chaos is None:
            return jax.vmap(lambda kk, ss: run(k=kk, s_init=ss))(k, s_init)
        chaos_b = jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.asarray(x), (T,)), chaos)
        return jax.vmap(
            lambda kk, ss, ch: run(k=kk, s_init=ss, chaos=ch))(
                k, s_init, chaos_b)

    from repro.kernels.packet_step import ops as _step_ops  # lazy: cycle

    H, N = pw.n_types, pw.n_jobs
    ring = resolve_ring(m_nodes, N, ring)
    R = resolve_max_requeues(chaos, N)
    L_cap = N + R
    budget = event_budget(N, R) if budget is None else max(1, int(budget))
    seg = SCAN_SEG if seg is None else max(1, int(seg))
    n_segs = -(-budget // seg)
    budget = n_segs * seg
    dtype = precision.canonical_dtype(pw.submit.dtype)
    k = jnp.asarray(k, dtype)
    s = jnp.asarray(s_init, dtype)
    m_nodes = jnp.asarray(m_nodes, jnp.int32)
    p_j = (jnp.ones((H,), dtype) if priority is None
           else jnp.asarray(priority, dtype))
    tmax_j = (jnp.full((H,), 3600.0, dtype) if t_max is None
              else jnp.asarray(t_max, dtype))
    key_pad = jnp.iinfo(jnp.int32).max

    if chaos is None:
        u1 = u2 = chaos_params = None
    else:
        chaos_b = jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.asarray(x), (T,)), chaos)
        u = jax.vmap(
            lambda c: chaos_uniforms(c, dtype, L_cap))(chaos_b)
        u1 = jnp.transpose(u[:, :, 0])          # [L_cap, T]
        u2 = jnp.transpose(u[:, :, 1])
        chaos_params = tuple(
            jnp.broadcast_to(jnp.asarray(x, dtype), (1, T))
            for x in (chaos.mtbf_chip_hours, chaos.ckpt_period,
                      chaos.straggler_prob, chaos.straggler_factor,
                      chaos.straggler_deadline))

    k_col = k[None, :]
    s_col = s[None, :]

    def lane_act(cols: _ScanState):
        act = ((cols.next_sub[0] < N) |
               jnp.any(~jnp.isinf(cols.grp_end), axis=0) |
               jnp.any(cols.tail > cols.head, axis=0))
        if chaos is not None:
            act = act | jnp.any(cols.pool_code > 0, axis=0)
        return act

    def step(cols: _ScanState, _):
        return _step_ops.fused_packet_step(
            pw, k_col, s_col, p_j, tmax_j, cols,
            u1=u1, u2=u2, chaos_params=chaos_params, r_cap=R)

    def seg_cond(carry):
        cols, _, s_idx = carry
        return jnp.any(lane_act(cols)) & (s_idx < n_segs)

    def seg_body(carry):
        cols, logs, s_idx = carry
        cols, ys = jax.lax.scan(step, cols, None, length=seg)
        off = s_idx * seg
        logs = tuple(
            jax.lax.dynamic_update_slice(buf, y[:, 0, :],
                                         (off, jnp.zeros_like(off)))
            for buf, y in zip(logs, ys))
        return cols, logs, s_idx + 1

    cols0 = _ScanState(
        t=jnp.zeros((1, T), dtype),
        next_sub=jnp.zeros((1, T), jnp.int32),
        head=jnp.zeros((H, T), jnp.int32),
        tail=jnp.zeros((H, T), jnp.int32),
        m_free=jnp.full((1, T), m_nodes, jnp.int32),
        grp_end=jnp.full((ring, T), INF, dtype),
        grp_m=jnp.zeros((ring, T), jnp.int32),
        qlen_int=jnp.zeros((1, T), dtype),
        busy_ns=jnp.zeros((1, T), dtype),
        useful_ns=jnp.zeros((1, T), dtype),
        n_groups=jnp.zeros((1, T), jnp.int32),
        pool_w=jnp.zeros((H, T), dtype),
        pool_oldest=jnp.full((H, T), INF, dtype),
        pool_code=jnp.zeros((H, T), jnp.int32),
        grp_jtype=jnp.zeros((ring, T), jnp.int32),
        grp_rem_w=jnp.zeros((ring, T), dtype),
        grp_rem_cnt=jnp.zeros((ring, T), jnp.int32),
        grp_rem_oldest=jnp.full((ring, T), INF, dtype),
        lost_work=jnp.zeros((1, T), dtype),
        failures=jnp.zeros((1, T), jnp.int32),
        straggler_kills=jnp.zeros((1, T), jnp.int32),
        requeues=jnp.zeros((1, T), jnp.int32),
        requeued_jobs=jnp.zeros((1, T), jnp.int32))
    logs0 = (jnp.full((budget, T), key_pad, jnp.int32),
             jnp.zeros((budget, T), dtype),
             jnp.zeros((budget, T), jnp.int32),
             jnp.zeros((budget, T), dtype))

    cols, logs, n_run = jax.lax.while_loop(
        seg_cond, seg_body, (cols0, logs0, jnp.zeros((), jnp.int32)))
    logs_lane = tuple(jnp.swapaxes(buf, 0, 1) for buf in logs)
    st_lane = _lane_cols_to_rows(cols)

    def assemble(lane_logs, st: _ScanState, s_lane):
        s_row = jnp.full((H,), s_lane, dtype)
        start_t, run_start_t = _reconstruct_job_times(pw, *lane_logs, s_row)
        drained = ((st.next_sub >= N) & jnp.all(jnp.isinf(st.grp_end)) &
                   jnp.all(st.head == st.tail))
        if chaos is not None:
            drained = drained & jnp.all(st.pool_code == 0)
        ok = drained & jnp.all(jnp.isfinite(start_t))
        return DesResult(start_t=start_t, run_start_t=run_start_t,
                         qlen_int=st.qlen_int, busy_ns=st.busy_ns,
                         useful_ns=st.useful_ns, n_groups=st.n_groups,
                         makespan=st.t, ok=ok, budget_exhausted=~drained,
                         lost_work=st.lost_work, failures=st.failures,
                         straggler_kills=st.straggler_kills,
                         requeues=st.requeues,
                         requeued_jobs=st.requeued_jobs)

    res = jax.vmap(assemble)(logs_lane, st_lane, s)
    return (res, jnp.full((T,), n_run)) if with_segments else res


# --------------------------------------------------------------------------
# Reference implementation: the original O(N)-masked-writes event body.
# Retained verbatim (fixed RING ring, eager per-job writes) as the oracle
# for the equivalence test suite and the baseline for benchmarks/bench_des.
# --------------------------------------------------------------------------

class _RefState(NamedTuple):
    t: jnp.ndarray
    next_sub: jnp.ndarray
    head: jnp.ndarray
    tail: jnp.ndarray
    m_free: jnp.ndarray
    grp_end: jnp.ndarray
    grp_m: jnp.ndarray
    start_t: jnp.ndarray      # [N] written eagerly per group — O(N)/event
    run_start_t: jnp.ndarray  # [N]
    qlen_int: jnp.ndarray
    busy_ns: jnp.ndarray
    useful_ns: jnp.ndarray
    n_groups: jnp.ndarray
    iters: jnp.ndarray


def simulate_packet_reference(pw: PackedWorkload, k, s_init, m_nodes,
                              priority=None, t_max=None,
                              max_iters: int | None = None) -> DesResult:
    """Seed-equivalent Packet DES with per-event O(N) metric writes."""
    H, N = pw.n_types, pw.n_jobs
    dtype = precision.canonical_dtype(pw.submit.dtype)
    k = jnp.asarray(k, dtype)
    s_init = jnp.asarray(s_init, dtype)
    m_nodes = jnp.asarray(m_nodes, jnp.int32)
    s_j = jnp.full((H,), s_init, dtype)
    p_j = jnp.ones((H,), dtype) if priority is None else jnp.asarray(priority, dtype)
    tmax_j = (jnp.full((H,), 3600.0, dtype) if t_max is None
              else jnp.asarray(t_max, dtype))
    if max_iters is None:
        max_iters = 4 * N + 64

    t_end_metric = pw.t_last_submit
    type_ids = jnp.arange(H)

    def sched_cond(st):
        nonempty = st.tail > st.head
        free_slot = jnp.any(jnp.isinf(st.grp_end))
        return (st.m_free > 0) & jnp.any(nonempty) & free_slot

    def sched_body(st: _RefState) -> _RefState:
        nonempty = st.tail > st.head
        sum_w = (pw.tj_prefw[type_ids, st.tail] -
                 pw.tj_prefw[type_ids, st.head])
        oldest = pw.tj_submit[type_ids, jnp.minimum(st.head, N - 1)]
        w = packet.queue_weights(sum_w, s_j, p_j, oldest, st.t, tmax_j, nonempty)
        j = jnp.argmax(w)
        work = sum_w[j]
        m_grp = packet.group_nodes(work, k, s_j[j], st.m_free)
        dur = packet.group_duration(work, s_j[j], m_grp)
        slot = jnp.argmax(jnp.isinf(st.grp_end))
        t_fin = st.t + dur

        in_grp = ((pw.jtype == j) & (pw.rank >= st.head[j]) &
                  (pw.rank < st.tail[j]))
        start_t = jnp.where(in_grp, st.t, st.start_t)
        head_w = pw.tj_prefw[j, st.head[j]]
        run_start = st.t + s_j[j] + (pw.cumw - head_w) / m_grp.astype(dtype)
        run_start_t = jnp.where(in_grp, run_start, st.run_start_t)

        busy = st.busy_ns + m_grp.astype(dtype) * _window_overlap(
            st.t, t_fin, t_end_metric)
        useful = st.useful_ns + m_grp.astype(dtype) * _window_overlap(
            st.t + s_j[j], t_fin, t_end_metric)

        return st._replace(
            head=st.head.at[j].set(st.tail[j]),
            m_free=st.m_free - m_grp,
            grp_end=st.grp_end.at[slot].set(t_fin),
            grp_m=st.grp_m.at[slot].set(m_grp),
            start_t=start_t, run_start_t=run_start_t,
            busy_ns=busy, useful_ns=useful,
            n_groups=st.n_groups + 1)

    def cond(st: _RefState):
        more = (st.next_sub < N) | jnp.any(~jnp.isinf(st.grp_end))
        return more & (st.iters < max_iters)

    def body(st: _RefState) -> _RefState:
        t_sub = jnp.where(st.next_sub < N,
                          pw.submit[jnp.minimum(st.next_sub, N - 1)], INF)
        slot = jnp.argmin(st.grp_end)
        t_fin = st.grp_end[slot]
        take_sub = t_sub <= t_fin
        t_new = jnp.where(take_sub, t_sub, t_fin)

        qlen = jnp.sum(st.tail - st.head).astype(st.t.dtype)
        qint = st.qlen_int + qlen * _window_overlap(st.t, t_new, t_end_metric)

        def on_submit(st):
            j = pw.jtype[jnp.minimum(st.next_sub, N - 1)]
            return st._replace(next_sub=st.next_sub + 1,
                               tail=st.tail.at[j].add(1))

        def on_finish(st):
            return st._replace(m_free=st.m_free + st.grp_m[slot],
                               grp_end=st.grp_end.at[slot].set(INF),
                               grp_m=st.grp_m.at[slot].set(0))

        st = st._replace(t=t_new, qlen_int=qint)
        st = jax.lax.cond(take_sub, on_submit, on_finish, st)
        st = jax.lax.while_loop(sched_cond, sched_body, st)
        return st._replace(iters=st.iters + 1)

    st0 = _RefState(
        t=jnp.zeros((), dtype), next_sub=jnp.zeros((), jnp.int32),
        head=jnp.zeros((H,), jnp.int32), tail=jnp.zeros((H,), jnp.int32),
        m_free=m_nodes, grp_end=jnp.full((RING,), INF, dtype),
        grp_m=jnp.zeros((RING,), jnp.int32),
        start_t=jnp.full((N,), INF, dtype), run_start_t=jnp.full((N,), INF, dtype),
        qlen_int=jnp.zeros((), dtype), busy_ns=jnp.zeros((), dtype),
        useful_ns=jnp.zeros((), dtype), n_groups=jnp.zeros((), jnp.int32),
        iters=jnp.zeros((), jnp.int32))

    st = jax.lax.while_loop(cond, body, st0)
    drained = (st.next_sub >= N) & jnp.all(jnp.isinf(st.grp_end)) & \
        jnp.all(st.head == st.tail)
    ok = drained & jnp.all(jnp.isfinite(st.start_t))
    zf = jnp.zeros((), dtype)
    zi = jnp.zeros((), jnp.int32)
    return DesResult(start_t=st.start_t, run_start_t=st.run_start_t,
                     qlen_int=st.qlen_int, busy_ns=st.busy_ns,
                     useful_ns=st.useful_ns, n_groups=st.n_groups,
                     makespan=st.t, ok=ok, budget_exhausted=~drained,
                     lost_work=zf, failures=zi, straggler_kills=zi,
                     requeues=zi, requeued_jobs=zi)


@partial(jax.jit, static_argnames=("max_iters", "ring"))
def _simulate_packet_jit(pw, k, s_init, m_nodes, max_iters=None, ring=None):
    return simulate_packet(pw, k, s_init, m_nodes, max_iters=max_iters,
                           ring=ring)


def simulate_packet_host(wl: Workload, k: float, s_prop: float,
                         dtype=jnp.float32) -> DesResult:
    """Convenience host entry point: workload + scale ratio + init proportion.

    Passing ``dtype=jnp.float64`` is the float64 opt-in: the whole
    pack-simulate pipeline runs inside a `precision.dtype_scope`, so the
    session's global x64 state is untouched.
    """
    with precision.dtype_scope(dtype):
        pw = pack_workload(wl, dtype)
        s = wl.init_time_for_proportion(s_prop)
        return jax.tree.map(np.asarray, simulate_packet(
            pw, k, s, wl.params.nodes))

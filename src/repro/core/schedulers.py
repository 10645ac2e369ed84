"""Baseline schedulers: FCFS and EASY backfill (rigid jobs).

The paper itself sweeps only the Packet algorithm; its predecessor work
([1], [4]) compares grouping against the backfill scheduling that production
JMS use. We implement both baselines on the *rigid* view of the workload
(each job runs alone on its requested n_i nodes for s + e_i seconds, paying
its own initialization), with the same fixed-shape `lax.while_loop` DES
skeleton as `repro.core.des` so results are directly comparable.

EASY backfilling is Lifka's (JSSPP 1995) as Mu'alem & Feitelson state it
(IEEE TPDS 12(6), 2001), with exact runtime estimates: the first waiting
job (the head) is reserved the *shadow time*, the earliest instant enough
nodes are free for it, and the nodes free then beyond its need are the
*extra* nodes. A job behind it starts now if it fits and either ends by
the shadow time or needs no more than the extra nodes that are left; a job
of the second kind uses up its share of them. So backfilling never delays
the head. Jobs are tested in submit order, at most `backfill_depth` a pass
(production schedulers bound this too, e.g. Slurm's ``bf_max_job_test``).

Per-event cost mirrors the group-log DES: the skeleton's queue-length
integral uses the scalar identity `waiting = next_sub - n_started` (no [N]
mask sum per event), FCFS walks a head pointer (jobs start strictly in
submit order, so the head is a monotone scalar — O(1) per started job
instead of an O(N) argmax), and the running-job ring is sized
`resolve_ring(M, N)` instead of a fixed 512. Backfill still scans the
waiting mask once per pass: its candidate set is inherently order-breaking.

Tie rules: a submission is taken before a completion at the same time;
simultaneous completions in ring-slot order (a job takes the lowest free
slot); candidates in submit order.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import precision
from repro.core.des import (DesResult, PackedWorkload, _window_overlap,
                            INF, resolve_ring)

#: the rigid schedulers, by the name `simulate_rigid` takes
RIGID_SCHEDULERS = ("fcfs", "backfill")


class RigidCounts(NamedTuple):
    """Per-lane trip counts of the rigid engine's loops."""
    trips: jnp.ndarray        # outer event trips (one event each)
    passes: jnp.ndarray       # backfill candidate passes run
    candidates: jnp.ndarray   # candidate trips that held a waiting job


class _BaseState(NamedTuple):
    t: jnp.ndarray
    next_sub: jnp.ndarray
    head_ptr: jnp.ndarray     # first never-started job index (monotone)
    started: jnp.ndarray      # [N] bool (submitted jobs that began running)
    m_free: jnp.ndarray
    grp_end: jnp.ndarray      # [ring]
    grp_m: jnp.ndarray        # [ring]
    start_t: jnp.ndarray      # [N]
    qlen_int: jnp.ndarray
    busy_ns: jnp.ndarray
    useful_ns: jnp.ndarray
    n_started: jnp.ndarray
    iters: jnp.ndarray
    passes: jnp.ndarray
    cands: jnp.ndarray


def _start_job(st: _BaseState, i, s_init, runtime, nodes, t_end_metric):
    """Start rigid job i now; returns updated state (assumes it fits)."""
    dtype = st.t.dtype
    dur = s_init + runtime[i]
    t_fin = st.t + dur
    slot = jnp.argmax(jnp.isinf(st.grp_end))
    m = nodes[i]
    busy = st.busy_ns + m.astype(dtype) * _window_overlap(st.t, t_fin, t_end_metric)
    useful = st.useful_ns + m.astype(dtype) * _window_overlap(
        st.t + s_init, t_fin, t_end_metric)
    return st._replace(
        started=st.started.at[i].set(True),
        m_free=st.m_free - m,
        grp_end=st.grp_end.at[slot].set(t_fin),
        grp_m=st.grp_m.at[slot].set(m),
        start_t=st.start_t.at[i].set(st.t),
        busy_ns=busy, useful_ns=useful,
        n_started=st.n_started + 1)


def _spend_extra(extra, m, started, ends_before):
    """The extra nodes left once a candidate of `m` nodes was tested: a job
    that started and runs past the shadow time uses `m` of them."""
    return jnp.where(started & ~ends_before, extra - m, extra)


def _event_skeleton(pw: PackedWorkload, s_init, m_nodes, sched_pass,
                    max_iters, ring):
    """Shared submit/finish event loop around a scheduling pass."""
    N = pw.n_jobs
    dtype = pw.submit.dtype
    t_end_metric = pw.t_last_submit

    def cond(st: _BaseState):
        more = (st.next_sub < N) | jnp.any(~jnp.isinf(st.grp_end))
        return more & (st.iters < max_iters)

    def body(st: _BaseState):
        t_sub = jnp.where(st.next_sub < N,
                          pw.submit[jnp.minimum(st.next_sub, N - 1)], INF)
        slot = jnp.argmin(st.grp_end)
        t_fin = st.grp_end[slot]
        take_sub = t_sub <= t_fin
        t_new = jnp.where(take_sub, t_sub, t_fin)

        # waiting jobs = submitted minus started, as a scalar counter
        n_wait = (st.next_sub - st.n_started).astype(dtype)
        qint = st.qlen_int + n_wait * _window_overlap(st.t, t_new, t_end_metric)
        st = st._replace(t=t_new, qlen_int=qint)

        st = jax.lax.cond(
            take_sub,
            lambda s: s._replace(next_sub=s.next_sub + 1),
            lambda s: s._replace(m_free=s.m_free + s.grp_m[slot],
                                 grp_end=s.grp_end.at[slot].set(INF),
                                 grp_m=s.grp_m.at[slot].set(0)),
            st)
        st = sched_pass(st)
        return st._replace(iters=st.iters + 1)

    zi = jnp.zeros((), jnp.int32)
    st0 = _BaseState(
        t=jnp.zeros((), dtype), next_sub=zi, head_ptr=zi,
        started=jnp.zeros((N,), bool), m_free=jnp.asarray(m_nodes, jnp.int32),
        grp_end=jnp.full((ring,), INF, dtype),
        grp_m=jnp.zeros((ring,), jnp.int32),
        start_t=jnp.full((N,), INF, dtype),
        qlen_int=jnp.zeros((), dtype), busy_ns=jnp.zeros((), dtype),
        useful_ns=jnp.zeros((), dtype), n_started=zi, iters=zi, passes=zi,
        cands=zi)

    st = jax.lax.while_loop(cond, body, st0)
    drained = (st.next_sub >= N) & jnp.all(jnp.isinf(st.grp_end))
    ok = drained & jnp.all(st.started)
    zf = jnp.zeros((), dtype)
    res = DesResult(start_t=st.start_t,
                    run_start_t=st.start_t + s_init,
                    qlen_int=st.qlen_int, busy_ns=st.busy_ns,
                    useful_ns=st.useful_ns, n_groups=st.n_started,
                    makespan=st.t, ok=ok, budget_exhausted=~drained,
                    lost_work=zf, failures=zi, straggler_kills=zi,
                    requeues=zi, requeued_jobs=zi)
    return res, RigidCounts(st.iters, st.passes, st.cands)


def _fcfs_pass(pw: PackedWorkload, s_init):
    """Strict FCFS: the head of the queue blocks everything behind it.

    FCFS starts jobs exactly in submit order, so `head_ptr` IS the head of
    the queue — the scheduling pass is O(1) per started job.
    """
    N = pw.n_jobs

    def cond(st):
        i = jnp.minimum(st.head_ptr, N - 1)
        fits = (st.head_ptr < st.next_sub) & (pw.nodes[i] <= st.m_free)
        return fits & jnp.any(jnp.isinf(st.grp_end))

    def body(st):
        i = jnp.minimum(st.head_ptr, N - 1)
        st = _start_job(st, i, s_init, pw.runtime, pw.nodes, pw.t_last_submit)
        return st._replace(head_ptr=st.head_ptr + 1)

    return lambda st: jax.lax.while_loop(cond, body, st)


def _backfill_pass(pw: PackedWorkload, s_init, backfill_depth: int):
    """EASY backfill (module docstring): the head loop, then one shadow
    time and one candidate pass of `backfill_depth` trips."""
    N = pw.n_jobs
    idx = jnp.arange(N)

    def sched_pass(st: _BaseState):
        # 1) start jobs from the head while they fit
        def head_cond(st):
            waiting = (idx < st.next_sub) & ~st.started
            head = jnp.argmax(waiting)
            fits = jnp.any(waiting) & (pw.nodes[head] <= st.m_free)
            return fits & jnp.any(jnp.isinf(st.grp_end))

        def head_body(st):
            waiting = (idx < st.next_sub) & ~st.started
            head = jnp.argmax(waiting)
            return _start_job(st, head, s_init, pw.runtime, pw.nodes,
                              pw.t_last_submit)

        st = jax.lax.while_loop(head_cond, head_body, st)

        # 2) a head that remains blocked is reserved the shadow time: the
        # first end, in end order, by which enough nodes are free for it
        waiting = (idx < st.next_sub) & ~st.started
        any_wait = jnp.any(waiting)
        head = jnp.argmax(waiting)
        n_head = jnp.where(any_wait, pw.nodes[head], 1)

        order = jnp.argsort(st.grp_end)                 # running jobs by end
        frees = jnp.cumsum(st.grp_m[order]) + st.m_free
        enough = frees >= n_head
        shadow = jnp.where(jnp.any(enough),
                           st.grp_end[order][jnp.argmax(enough)], INF)
        # every node free at the shadow time that the head leaves over
        at_shadow = st.m_free + jnp.sum(jnp.where(st.grp_end <= shadow,
                                                  st.grp_m, 0))
        extra = jnp.maximum(at_shadow - n_head, 0)

        # 3) up to backfill_depth waiting jobs behind the head
        cand = jnp.nonzero(waiting & (idx != head), size=backfill_depth,
                           fill_value=N)[0]

        def bf_body(q, carry):
            st, extra = carry
            i = cand[q]
            valid = i < N
            i_c = jnp.minimum(i, N - 1)
            m = pw.nodes[i_c]
            # the job's own end, as `_start_job` computes it
            ends_before = st.t + (s_init + pw.runtime[i_c]) <= shadow
            do = (valid & (m <= st.m_free) & jnp.any(jnp.isinf(st.grp_end))
                  & (ends_before | (m <= extra)))
            st = jax.lax.cond(
                do,
                lambda s: _start_job(s, i_c, s_init, pw.runtime, pw.nodes,
                                     pw.t_last_submit),
                lambda s: s, st)
            return (st._replace(cands=st.cands + valid.astype(jnp.int32)),
                    _spend_extra(extra, m, do, ends_before))

        st, _ = jax.lax.fori_loop(0, backfill_depth, bf_body, (st, extra))
        return st._replace(passes=st.passes + 1)

    return sched_pass


def simulate_rigid(pw: PackedWorkload, s_init, m_nodes, scheduler: str,
                   backfill_depth: int = 64, max_iters: int | None = None,
                   ring: int | None = None) -> tuple[DesResult, RigidCounts]:
    """One rigid-scheduler run (`scheduler` in `RIGID_SCHEDULERS`): its
    result and the per-lane trip counts of its loops."""
    if scheduler not in RIGID_SCHEDULERS:
        raise ValueError(f"scheduler must be one of {RIGID_SCHEDULERS}, "
                         f"got {scheduler!r}")
    N = pw.n_jobs
    s_init = jnp.asarray(s_init, precision.canonical_dtype(pw.submit.dtype))
    ring = resolve_ring(m_nodes, N, ring)
    if max_iters is None:
        max_iters = 4 * N + 64
    sched_pass = (_fcfs_pass(pw, s_init) if scheduler == "fcfs"
                  else _backfill_pass(pw, s_init, backfill_depth))
    return _event_skeleton(pw, s_init, m_nodes, sched_pass, max_iters, ring)


def simulate_fcfs(pw: PackedWorkload, s_init, m_nodes,
                  max_iters: int | None = None,
                  ring: int | None = None) -> DesResult:
    """Strict FCFS: jobs start in submit order (`_fcfs_pass`)."""
    return simulate_rigid(pw, s_init, m_nodes, "fcfs", max_iters=max_iters,
                          ring=ring)[0]


def simulate_backfill(pw: PackedWorkload, s_init, m_nodes,
                      backfill_depth: int = 64,
                      max_iters: int | None = None,
                      ring: int | None = None) -> DesResult:
    """EASY backfill (module docstring), testing at most `backfill_depth`
    waiting jobs behind the head per pass."""
    return simulate_rigid(pw, s_init, m_nodes, "backfill",
                          backfill_depth=backfill_depth,
                          max_iters=max_iters, ring=ring)[0]

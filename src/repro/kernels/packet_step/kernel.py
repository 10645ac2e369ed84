"""Fused per-event DES step — Pallas kernel, lanes on the minor axis.

One invocation advances EVERY lane of a dispatch by one event: the
branchless select between group formation and event consumption, the
group-ring commit (including the packed requeue span stash from the
chaos engine), chaos outcome resolution, and the metric accumulates —
the body of `repro.core.des.packet_scan_step`, vectorized over a
trailing lane axis T. State is carried as [state, T] columns (scalars
as [1, T], per-type rows as [H, T], ring rows as [ring, T]) so the
select/commit chain of a step stays in kernel memory instead of
round-tripping each small intermediate through HBM.

Mosaic form: every per-lane value is a [1, T] row and every access to
the small state axes (types H, ring slots) is an iota-compare select —
`_first` for argmax/argmin, `_pick` for a row read, masked `where` for a
row write — which is exact and lowers on the TPU. The helpers live in
`repro.core.des`, whose XLA step uses the same form. Lookups along the job
axis (the per-type prefix tables at head/tail, the next submission, the
chaos draws of the forming group and the finish event's credit walk)
are gathered by `ops.step_gathers` in the enclosing XLA program before
the call: each of their indices is a function of the state at the start
of the step.

Bitwise contract: every float op here is elementwise, every reduction
is an integer max/min/sum or a float max/min over the state axis, and
every select returns the selected element unchanged, so per-lane results
are bit-identical to the scalar `packet_scan_step` (ref.py) in both
dtypes, chaos on and off — tests/test_packet_step.py pins this through
the interpret path, which discharges the kernel back into the enclosing
XLA program on CPU.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import packet
from repro.core.des import (CREDIT_EPS, INF, ChaosConfig, _ScanState,
                            _chaos_outcome, _first, _pick, _rows,
                            _window_overlap)

#: number of _ScanState fields carried as [*, T] columns
N_STATE_COLS = len(_ScanState._fields)


class StepGathers(NamedTuple):
    """Job-axis lookups of one step, gathered in XLA ([H, T] or [1, T])."""
    pf_tail: jnp.ndarray    # [H, T] tj_prefw[h, tail[h]]
    pf_head: jnp.ndarray    # [H, T] tj_prefw[h, head[h]]
    ts_head: jnp.ndarray    # [H, T] tj_submit[h, min(head[h], N - 1)]
    t_sub: jnp.ndarray      # [1, T] next submit time (+inf once drained)
    sub_j: jnp.ndarray      # [1, T] type of the next submission


class ChaosGathers(NamedTuple):
    """Chaos-path lookups of one step, gathered in XLA."""
    u1: jnp.ndarray         # [1, T] straggler draw of the forming group
    u2: jnp.ndarray         # [1, T] failure draw of the forming group
    pf_qlo: jnp.ndarray     # [H, T] tj_prefw[h, pool head or head[h]]
    p_cnt: jnp.ndarray      # [H, T] _pool_decode(pool_code) count
    p_lo: jnp.ndarray       # [H, T] ... span head rank
    p_frag: jnp.ndarray     # [H, T] ... fragmented bit (int32 0/1)
    rem_cnt: jnp.ndarray    # [1, T] _resolve_remnant at the finishing slot
    rem_w: jnp.ndarray
    rem_old: jnp.ndarray
    rem_lo: jnp.ndarray
    rem_hi: jnp.ndarray
    rem_walk: jnp.ndarray   # int32 0/1


def _any(x):
    return jnp.max(x.astype(jnp.int32), axis=0, keepdims=True) > 0


def event_step_kernel(*refs, n_jobs: int, r_cap: int, has_chaos: bool,
                      interpret: bool):
    """Pallas kernel body. Operand order (built by ops.fused_packet_step):

    inputs:  k [1, T], s [1, T], p_j [H, 1], tmax_j [H, 1], t_last [1, 1],
             the StepGathers fields, then iff has_chaos the five fault
             parameter columns [1, T] (mtbf, ckpt, prob, factor, deadline)
             and the ChaosGathers fields, then the state columns in
             _ScanState order.
    outputs: the updated state columns (aliased onto the inputs), then
             the 4 log records (key, t, m, head_w) as [1, T].

    The chaos path's optimization barriers pin XLA's fusion, so they are
    traced only when `interpret` discharges the body into XLA; Mosaic
    lowers no barrier.
    """
    N = n_jobs
    n_in = len(refs) - N_STATE_COLS - 4
    vals = [r[...] for r in refs[:n_in]]
    k, s, p_j, tmax_j, t_last = vals[:5]
    off = 5
    g = StepGathers(*vals[off:off + len(StepGathers._fields)])
    off += len(StepGathers._fields)
    if has_chaos:
        chaos = ChaosConfig(*vals[off:off + 5])
        off += 5
        cg = ChaosGathers(*vals[off:off + len(ChaosGathers._fields)])
        off += len(ChaosGathers._fields)
    st = _ScanState(*vals[off:n_in])
    out = refs[n_in:n_in + N_STATE_COLS]
    y_out = refs[n_in + N_STATE_COLS:]

    H = st.head.shape[0]
    ring = st.grp_end.shape[0]
    dtype = st.t.dtype
    t = st.t
    key_pad = jnp.iinfo(jnp.int32).max
    zero_f = jnp.zeros((), dtype)
    zero_i = jnp.zeros((), jnp.int32)
    one_i = jnp.ones((), jnp.int32)

    nonempty = st.tail > st.head                             # [H, T]
    if has_chaos:
        nonempty = nonempty | (st.pool_code > 0)
    free_mask = jnp.isinf(st.grp_end)                        # [ring, T]
    queued = _any(nonempty)                                  # [1, T]
    active = ((st.next_sub < N) | _any(~free_mask) |
              _any(st.tail > st.head))
    if has_chaos:
        active = active | _any(st.pool_code > 0)
    can_sched = (st.m_free > 0) & queued & _any(free_mask)
    do_sched = active & can_sched
    do_event = active & ~can_sched

    # greedy scheduling pass (paper Steps 1-5), masked unless do_sched
    sum_w = g.pf_tail - g.pf_head                            # [H, T]
    oldest = g.ts_head
    if has_chaos:
        sum_w = sum_w + st.pool_w
        oldest = jnp.minimum(oldest, st.pool_oldest)
    w = packet.queue_weights(sum_w, s, p_j, oldest, t, tmax_j, nonempty)
    j = _first(w, jnp.max)                                   # [1, T]
    at_j = _rows(H, j)
    work = _pick(sum_w, at_j)
    m_grp = packet.group_nodes(work, k, s, st.m_free)
    dur = packet.group_duration(work, s, m_grp)
    sslot = _first(free_mask.astype(jnp.int32), jnp.max)
    at_s = _rows(ring, sslot)
    head_w = _pick(g.pf_head, at_j)
    tail_j = _pick(st.tail, at_j)
    head_j = _pick(st.head, at_j)
    if not has_chaos:
        t_gfin = t + dur
        useful_end = t_gfin
    else:
        out_c = _chaos_outcome(chaos, cg.u1, cg.u2, st.requeues < r_cap, s,
                               work, m_grp, dur, dtype, barrier=interpret)
        t_gfin = t + out_c.dur
        useful_end = jnp.where(out_c.failed,
                               t + s + out_c.ckpt_done, t_gfin)
        requeued = do_sched & (out_c.failed | out_c.killed)
        eps = jnp.asarray(CREDIT_EPS, dtype)
        p_cnt = _pick(cg.p_cnt, at_j)
        has_pool = p_cnt > 0
        qlo = jnp.where(has_pool, _pick(cg.p_lo, at_j), head_j)
        res0 = jnp.where(has_pool, jnp.maximum(
            head_w - _pick(cg.pf_qlo, at_j) - _pick(st.pool_w, at_j),
            zero_f), zero_f)
        walk_ok = ~(has_pool & (_pick(cg.p_frag, at_j) > 0))
        avail = res0 + out_c.credit
        span_code = 1 + qlo * (N + 1) + tail_j
        rem_agg = work - out_c.credit
        a_has = requeued & (rem_agg > eps)
        a_cnt = (tail_j - head_j) + p_cnt
        code = jnp.where(requeued & walk_ok, span_code,
                         jnp.where(a_has, -a_cnt, zero_i))
        stash_w = jnp.where(
            requeued & walk_ok, avail,
            jnp.where(a_has, jnp.maximum(rem_agg, zero_f), zero_f))
        stash_old = jnp.where(a_has & ~walk_ok, _pick(oldest, at_j), INF)
    busy_inc = m_grp.astype(dtype) * _window_overlap(t, t_gfin, t_last)
    useful_inc = m_grp.astype(dtype) * _window_overlap(
        t + s, useful_end, t_last)
    if has_chaos and interpret:
        busy_inc, useful_inc = jax.lax.optimization_barrier(
            (busy_inc, useful_inc))

    # event step (submission or completion), masked unless do_event
    eslot = _first(st.grp_end, jnp.min)
    at_e = _rows(ring, eslot)
    t_efin = _pick(st.grp_end, at_e)
    take_sub = g.t_sub <= t_efin
    t_new = jnp.where(take_sub, g.t_sub, t_efin)
    qlen = jnp.sum(st.tail - st.head, axis=0, keepdims=True).astype(dtype)
    if has_chaos:
        qlen = qlen + jnp.sum(cg.p_cnt, axis=0, keepdims=True).astype(dtype)
    q_inc = qlen * _window_overlap(t, t_new, t_last)
    if has_chaos and interpret:
        q_inc = jax.lax.optimization_barrier(q_inc)

    do_submit = do_event & take_sub
    do_finish = do_event & ~take_sub
    set_j = at_j & do_sched
    set_s = at_s & do_sched
    clr_e = at_e & do_finish

    new = dict(
        t=jnp.where(do_event, t_new, t),
        next_sub=st.next_sub + jnp.where(do_submit, one_i, zero_i),
        head=jnp.where(set_j, st.tail, st.head),
        tail=st.tail + jnp.where(_rows(H, g.sub_j) & do_submit,
                                 one_i, zero_i),
        m_free=(st.m_free - jnp.where(do_sched, m_grp, zero_i)
                + jnp.where(do_finish, _pick(st.grp_m, at_e), zero_i)),
        grp_end=jnp.where(clr_e, INF,
                          jnp.where(set_s, t_gfin, st.grp_end)),
        grp_m=jnp.where(clr_e, zero_i, jnp.where(set_s, m_grp, st.grp_m)),
        qlen_int=st.qlen_int + jnp.where(do_event, q_inc, zero_f),
        busy_ns=st.busy_ns + jnp.where(do_sched, busy_inc, zero_f),
        useful_ns=st.useful_ns + jnp.where(do_sched, useful_inc, zero_f),
        n_groups=st.n_groups + jnp.where(do_sched, one_i, zero_i))

    if has_chaos:
        # finish merges the resolved requeue remnant (ops.step_gathers ran
        # the deferred credit walk) back into the per-type pool — same
        # chain as packet_scan_step, per lane
        j_f = _pick(st.grp_jtype, at_e)
        at_f = _rows(H, j_f)
        cnt_r = cg.rem_cnt
        old_cnt = _pick(cg.p_cnt, at_f)
        inc = do_finish & (cnt_r > 0)
        was_empty = old_cnt == 0
        contig = cg.rem_hi == _pick(st.head, at_f)
        old_frag = _pick(cg.p_frag, at_f) > 0
        # where(inc, old_frag | x, old_frag) as logic: Mosaic selects no i1
        frag = old_frag | (inc & ((cg.rem_walk == 0) | ~was_empty | ~contig))
        new_lo = jnp.where(was_empty, cg.rem_lo,
                           jnp.minimum(_pick(cg.p_lo, at_f), cg.rem_lo))
        new_code = ((new_lo * 2 + frag.astype(jnp.int32))
                    * (N + 1) + old_cnt + cnt_r)
        pool_w = jnp.where(set_j, zero_f, st.pool_w)
        pool_oldest = jnp.where(set_j, INF, st.pool_oldest)
        pool_code = jnp.where(set_j, zero_i, st.pool_code)
        new.update(
            pool_w=jnp.where(at_f, pool_w + jnp.where(
                do_finish, cg.rem_w, zero_f), pool_w),
            pool_oldest=jnp.where(at_f, jnp.minimum(pool_oldest, jnp.where(
                do_finish, cg.rem_old, INF)), pool_oldest),
            pool_code=jnp.where(at_f & inc, new_code, pool_code),
            grp_jtype=jnp.where(set_s, j, st.grp_jtype),
            grp_rem_w=jnp.where(clr_e, zero_f,
                                jnp.where(set_s, stash_w, st.grp_rem_w)),
            grp_rem_cnt=jnp.where(clr_e, zero_i,
                                  jnp.where(set_s, code, st.grp_rem_cnt)),
            grp_rem_oldest=jnp.where(clr_e, INF, jnp.where(
                set_s, stash_old, st.grp_rem_oldest)),
            lost_work=st.lost_work + jnp.where(do_sched, out_c.lost, zero_f),
            failures=st.failures + jnp.where(do_sched & out_c.failed,
                                             one_i, zero_i),
            straggler_kills=st.straggler_kills + jnp.where(
                do_sched & out_c.killed & ~out_c.failed, one_i, zero_i),
            requeues=st.requeues + jnp.where(requeued, one_i, zero_i),
            requeued_jobs=st.requeued_jobs + jnp.where(
                do_finish, cnt_r, zero_i))

    for ref, name in zip(out, _ScanState._fields):
        ref[...] = new.get(name, getattr(st, name))
    y_out[0][...] = jnp.where(do_sched, j * (N + 1) + tail_j, key_pad)
    y_out[1][...] = jnp.where(do_sched, t, zero_f)
    y_out[2][...] = jnp.where(do_sched, m_grp, zero_i)
    y_out[3][...] = jnp.where(do_sched, head_w, zero_f)

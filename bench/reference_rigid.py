"""The plain reference of the rigid baselines: FCFS and EASY backfill.

One run (a flow, an init time s and a scheduler) as an event-driven loop in
plain Python, independent of the code under test: no import of it, no
table it built. Each job runs alone on its n_i nodes for s + e_i: its own
initialisation, then its runtime.

The policies, as published:

* FCFS: jobs start in submit order; the first waiting job (the head)
  blocks every job behind it.
* EASY backfill (Lifka, JSSPP 1995; Mu'alem & Feitelson, IEEE TPDS 12(6),
  2001), with exact runtime estimates: the head starts while it fits. A
  head that does not fit is reserved the *shadow time*, the earliest end
  of a running job by which enough nodes are free for it (running jobs
  taken in order of end), and the nodes free at the shadow time beyond its
  need are the *extra* nodes. Then up to `depth` waiting jobs behind the
  head are tested, in submit order: a job that fits the free nodes starts
  if it ends by the shadow time, or, if it needs no more than the extra
  nodes left, starts and takes its nodes from them. So no backfilled job
  delays the head.

Events are submissions and completions, each followed by a scheduling pass.
Tie rules (the program's, `repro.core.schedulers`):

* a submission at the same time as a completion comes first;
* simultaneous completions are taken in the order of their slots: a job
  takes the lowest free of `slots` slots, freed at its completion;
* candidates are tested in submit order.

Metrics follow the paper (§3), measured over [0, last submit].

Precision: the loop computes in float64 (Python floats). `rnd` rounds every
input and every arithmetic result, in the order the program adds (a job
ends at t + (s + e); it is tested against the shadow time by that end), so
that with float32 rounding its decisions are float32's. The lower-precision
control is computed from the same code.
"""
from __future__ import annotations

import heapq
import math

import numpy as np

SCHEDULERS = ("fcfs", "backfill")


def simulate(submit, runtime, nodes, m_nodes: int, s: float, scheduler: str,
             depth: int = 64, slots: int | None = None, rnd=None) -> dict:
    """Run one rigid experiment; return its metrics, each job's start time
    (``start``), and its step count (every event: 2N once drained)."""
    if scheduler not in SCHEDULERS:
        raise ValueError(f"unknown scheduler {scheduler!r}")
    r = rnd if rnd is not None else (lambda x: x)
    n = len(submit)
    sub = [r(float(x)) for x in submit]
    rt = [r(float(x)) for x in runtime]
    nd = [int(x) for x in nodes]
    s = r(float(s))
    t_end = sub[-1] if n else 0.0
    slots = min(m_nodes, n) if slots is None else slots

    start = [math.inf] * n
    waiting = []                                # job indices, submit order
    running = []                                # heap of (end, slot, nodes)
    free_slots = list(range(slots))             # heap
    t = qint = busy = useful = 0.0
    m_free = m_nodes

    def overlap(a, b):
        return max(r(min(b, t_end) - min(a, t_end)), 0.0)

    def end_of(i):
        return r(t + r(s + rt[i]))

    def fits(i):
        return nd[i] <= m_free and bool(free_slots)

    def begin(i):
        nonlocal m_free, busy, useful
        t_fin = end_of(i)
        heapq.heappush(running, (t_fin, heapq.heappop(free_slots), nd[i]))
        m_free -= nd[i]
        busy = r(busy + r(nd[i] * overlap(t, t_fin)))
        useful = r(useful + r(nd[i] * overlap(r(t + s), t_fin)))
        start[i] = t

    def start_heads():
        k = 0
        while k < len(waiting) and fits(waiting[k]):
            begin(waiting[k])
            k += 1
        del waiting[:k]

    def backfill():
        start_heads()
        if not waiting:
            return
        need = nd[waiting[0]]
        shadow, free = math.inf, m_free
        for end, _, m in sorted(running):
            free += m
            if free >= need:
                shadow = end
                break
        extra = max(m_free + sum(m for end, _, m in running
                                 if end <= shadow) - need, 0)
        began = False
        for i in waiting[1:depth + 1]:
            if not fits(i):
                continue
            ends_before = end_of(i) <= shadow
            if ends_before or nd[i] <= extra:
                begin(i)
                began = True
                if not ends_before:
                    extra -= nd[i]
        if began:
            waiting[:] = [i for i in waiting if start[i] == math.inf]

    sched = start_heads if scheduler == "fcfs" else backfill
    nxt = steps = 0
    while True:
        t_sub = sub[nxt] if nxt < n else math.inf
        t_fin = running[0][0] if running else math.inf
        if t_sub == math.inf and t_fin == math.inf:
            break
        t_new = t_sub if t_sub <= t_fin else t_fin
        qint = r(qint + r(len(waiting) * overlap(t, t_new)))
        t = t_new
        steps += 1
        if t_sub <= t_fin:
            waiting.append(nxt)
            nxt += 1
        else:
            _, slot, m = heapq.heappop(running)
            m_free += m
            heapq.heappush(free_slots, slot)
        sched()

    window = max(t_end, 1e-9)
    wait = [max(r(a - b), 0.0) for a, b in zip(start, sub)]
    run_wait = [max(r(r(a + s) - b), 0.0) for a, b in zip(start, sub)]
    return {
        "avg_wait": float(np.mean(wait)) if n else 0.0,
        "med_wait": float(np.median(wait)) if n else 0.0,
        "avg_qlen": qint / window,
        "full_util": busy / (m_nodes * window),
        "useful_util": useful / (m_nodes * window),
        "avg_run_wait": float(np.mean(run_wait)) if n else 0.0,
        "ok": all(math.isfinite(x) for x in start),
        "start": np.asarray(start, np.float64),
        "steps": steps,
    }

"""The plain reference of the Packet group scheduler (paper §5).

One experiment (a flow, a scale ratio k and an init time s) as an
event-driven loop in plain Python, written from the paper's description and
independent of the code under test: no import of it, no table it built.

The policy, as the paper states it:

* jobs of each type wait in that type's queue, in submit order;
* while nodes are free and a queue holds jobs, the queue of largest weight
  W_j = (sum of queued work / s) * P_j * (1 + T_cur / T_max), with the
  configuration's priority P_j (the same for every type) and T_max,
  T_cur the age of its oldest job, is drained whole into one group (the
  first such queue on a tie);
* the group takes m = min(max(ceil(work / (k s)), 1), free nodes) nodes
  and runs for s + work / m: one initialisation, then its jobs back to back
  with linear speed-up;
* events are job submissions and group completions; a submission at the
  same time as a completion comes first, and simultaneous completions are
  taken in the order their groups were placed in the lowest free slot of
  `slots` slots (a slot is freed at completion).

Metrics follow the paper (§3), measured over [0, last submit].

Precision: the loop computes in float64 (Python floats). `rnd` rounds every
input and every arithmetic result, which is how the lower-precision control
(float32 or bfloat16) is computed from the same code.
"""
from __future__ import annotations

import heapq
import math

import numpy as np

T_MAX = 3600.0


def rounder(dtype):
    """A function that rounds a Python float to `dtype` (None: float64)."""
    if dtype == "bfloat16":
        import ml_dtypes
        dtype = ml_dtypes.bfloat16
    if dtype is None or np.dtype(dtype) == np.float64:
        return None
    dt = np.dtype(dtype)
    return lambda x: float(dt.type(x))


def init_time(runtime, s_prop: float) -> float:
    """Init time s giving an average init proportion S: s = S/(1-S) * mean(e)."""
    return float(s_prop / (1.0 - s_prop) * np.mean(np.asarray(runtime,
                                                               np.float64)))


def simulate(submit, work, jtype, n_types: int, m_nodes: int, k: float,
             s: float, slots: int | None = None, rnd=None,
             fail=None, t_max: float = T_MAX,
             priority: float = 1.0) -> dict:
    """Run one Packet experiment; return its metrics and its step count.

    `fail` (tests only) maps the index of a formed group to the share of its
    work that is credited; the rest re-enters its type's queue as one
    requeued batch when the group completes, which then forms a further
    group. `steps` counts the loop's three kinds of step: a submission
    consumed, a group formed, a group completed.
    """
    r = rnd if rnd is not None else (lambda x: x)
    n = len(submit)
    sub = [r(float(x)) for x in submit]
    wk = [r(float(x)) for x in work]
    typ = [int(x) for x in jtype]
    k, s = r(float(k)), r(float(s))
    t_max, prio = r(float(t_max)), r(float(priority))
    t_end = sub[-1] if n else 0.0
    slots = m_nodes if slots is None else slots

    queue = [[] for _ in range(n_types)]       # job indices, submit order
    qwork = [0.0] * n_types
    pool = [[0, 0.0, math.inf] for _ in range(n_types)]  # count, work, oldest
    start = [math.inf] * n
    run_start = [math.inf] * n
    running = []                               # (end, slot, m, type, rem)
    free_slots = list(range(slots))
    m_free = m_nodes
    t = 0.0
    nxt = 0
    qint = busy = useful = 0.0
    n_groups = steps = 0
    queued = 0                                  # jobs waiting, pools included

    def overlap(a, b):
        return max(r(min(b, t_end) - min(a, t_end)), 0.0)

    while True:
        # greedy scheduling pass
        while m_free > 0 and free_slots and queued > 0:
            best, j = -math.inf, -1
            for h in range(n_types):
                if not queue[h] and pool[h][0] == 0:
                    continue
                sw = r(qwork[h] + pool[h][1]) if pool[h][0] else qwork[h]
                oldest = sub[queue[h][0]] if queue[h] else math.inf
                oldest = min(oldest, pool[h][2])
                age = max(r(t - oldest), 0.0)
                w = r(r(r(sw / s) * prio) * r(1.0 + r(age / t_max)))
                if w > best:
                    best, j = w, h
            work_g = r(qwork[j] + pool[j][1]) if pool[j][0] else qwork[j]
            m = min(max(int(math.ceil(r(work_g / r(k * s)))), 1), m_free)
            dur = r(s + r(work_g / m))
            acc = 0.0
            for i in queue[j]:
                start[i] = t
                run_start[i] = r(r(t + s) + r(acc / m))
                acc = r(acc + wk[i])
            rem = 0.0
            if fail is not None:
                rem = r(work_g * (1.0 - fail(n_groups)))
            n_members = len(queue[j]) + pool[j][0]
            queued -= n_members
            busy = r(busy + r(m * overlap(t, r(t + dur))))
            useful = r(useful + r(m * overlap(r(t + s), r(t + dur))))
            slot = heapq.heappop(free_slots)
            heapq.heappush(running, (r(t + dur), slot, m, j,
                                     (n_members, rem, t) if rem > 0 else None))
            m_free -= m
            queue[j] = []
            qwork[j] = 0.0
            pool[j] = [0, 0.0, math.inf]
            n_groups += 1
            steps += 1
        t_sub = sub[nxt] if nxt < n else math.inf
        t_fin = running[0][0] if running else math.inf
        if t_sub == math.inf and t_fin == math.inf:
            break
        t_new = t_sub if t_sub <= t_fin else t_fin
        qint = r(qint + r(queued * overlap(t, t_new)))
        t = t_new
        steps += 1
        if t_sub <= t_fin:
            h = typ[nxt]
            queue[h].append(nxt)
            qwork[h] = r(qwork[h] + wk[nxt])
            queued += 1
            nxt += 1
        else:
            _, slot, m, h, requeue = heapq.heappop(running)
            m_free += m
            heapq.heappush(free_slots, slot)
            if requeue is not None:
                cnt, rem, oldest = requeue
                p = pool[h]
                pool[h] = [p[0] + cnt, r(p[1] + rem), min(p[2], oldest)]
                queued += cnt

    window = max(t_end, 1e-9)
    wait = [max(r(a - b), 0.0) for a, b in zip(start, sub)]
    run_wait = [max(r(a - b), 0.0) for a, b in zip(run_start, sub)]
    return {
        "avg_wait": float(np.mean(wait)) if n else 0.0,
        "med_wait": float(np.median(wait)) if n else 0.0,
        "avg_qlen": qint / window,
        "full_util": busy / (m_nodes * window),
        "useful_util": useful / (m_nodes * window),
        "avg_run_wait": float(np.mean(run_wait)) if n else 0.0,
        "n_groups": n_groups,
        "ok": all(math.isfinite(x) for x in start),
        "steps": steps,
    }


def lane_events(n_jobs: int, n_groups) -> np.ndarray:
    """Steps a lane needs, read from its own outputs: every job is submitted
    once, and every group formed (requeued batches included) completes once."""
    return int(n_jobs) + 2 * np.asarray(n_groups, np.int64)

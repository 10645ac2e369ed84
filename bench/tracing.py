"""From a profiler trace to device busy time, idle gaps and top device ops.

`read_xplane` turns the JAX profiler's `.xplane.pb` into plain interval
lists; every other function works on those lists, so the reduction is
checked on small synthetic traces (`tests/test_bench_tracing.py`).

* busy: the union of the intervals in which a program ran on a device;
* idle share: 1 - busy / window, with busy and window summed over devices;
* idle gaps: the complement of the busy union inside the window, each named
  by the innermost host span (`jax.profiler.TraceAnnotation`) that was open
  at the gap's midpoint, or ``host:none``, and summed per name (device
  seconds, over all devices);
* device ops: time per program name, summed over devices.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os

#: the line of a device plane with one event per program execution
MODULES_LINE = "XLA Modules"
#: where a device plane says that its trace buffer ran out
DROP_LINE, DROPPED = "XLA TraceMe", "Trace Buffers Dropped"
#: prefix of the host spans the benchmark writes
SPAN_PREFIX = "bench."


def op_name(text: str) -> str:
    """``jit__packet_lanes`` from ``jit__packet_lanes(123...)``, and
    ``%while.321`` from an op's HLO text (``%while.321 = (...)``)."""
    text = text.split(" = ", 1)[0].strip()
    return text.split("(", 1)[0] if text.endswith(")") else text


def read_xplane(logdir: str):
    """``(devices, spans, cut_ns)`` from the newest trace under `logdir`.

    `devices` maps a device plane's name to its ``[(program, start_ns,
    end_ns)]``, one per program execution (the plane's ``XLA Modules``
    line); `spans` is ``[(name, start_ns, end_ns)]`` of the benchmark's
    host spans. Where a device ran out of trace buffer, its record stops
    early: `cut_ns` is then the end of the last program any such device
    recorded (None where no buffer was dropped).
    """
    import jax
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, spans, cut = {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, dropped = [], False
            for line in plane.lines:
                if line.name == DROP_LINE:
                    dropped = any(DROPPED in ev.name for ev in line.events)
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append((op_name(ev.name), s,
                                s + int(ev.duration_ns)))
            if ops:
                devices[plane.name] = ops
                if dropped:
                    end = max(e for _, _, e in ops)
                    cut = end if cut is None else min(cut, end)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    return devices, spans, cut


def union(intervals, lo=None, hi=None):
    """Sorted disjoint ``[(start, end)]`` covering `intervals`, clipped to
    ``[lo, hi]`` where given."""
    out = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(intervals, lo=None, hi=None) -> int:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals, lo, hi):
    """Idle ``[(start, end)]`` inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_timeline(spans):
    """``(starts, names)``: from each start on, until the next, the innermost
    open span (the one opened last) is ``names[i]`` (None: no span open)."""
    marks = sorted([(e, 0, i) for i, (_, s, e) in enumerate(spans)]
                   + [(s, 1, i) for i, (_, s, e) in enumerate(spans)])
    heap, closed, cuts, names = [], set(), [], []
    for t, kind, i in marks:
        if kind:
            heapq.heappush(heap, (-spans[i][1], i))
        else:
            closed.add(i)
        while heap and heap[0][1] in closed:
            heapq.heappop(heap)
        name = spans[heap[0][1]][0] if heap else None
        if cuts and cuts[-1] == t:
            names[-1] = name
        else:
            cuts.append(t)
            names.append(name)
    return cuts, names


def span_at(timeline, t):
    """The innermost span open at time `t`, or None."""
    cuts, names = timeline
    i = bisect.bisect_right(cuts, t) - 1
    return names[i] if i >= 0 else None


def reduce(devices: dict, spans, lo: int, hi: int, top: int = 10) -> dict:
    """Busy and window seconds (averaged over devices), the idle share in
    percent, and the `breakdown`: idle time by host span and top ops."""
    n = max(len(devices), 1)
    window = (hi - lo) * 1e-9
    busy = [busy_ns(ops, lo, hi) * 1e-9 for ops in devices.values()]
    busy_s = sum(busy) / n
    timeline = span_timeline(spans)
    by_gap = {}
    for ops in devices.values():
        for s, e in gaps(ops, lo, hi):
            name = span_at(timeline, (s + e) // 2) or "host:none"
            by_gap.setdefault(name, []).append((e - s) * 1e-9)
    idle_by_span = sorted(((k, sum(v)) for k, v in by_gap.items()),
                          key=lambda x: -x[1])[:top]
    per_op = {}
    for ops in devices.values():
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                per_op[name] = per_op.get(name, 0.0) + (e - s) * 1e-9
    device_ops = sorted(per_op.items(), key=lambda x: -x[1])[:top]
    return {
        "n_devices": len(devices),
        "busy_s": busy_s,
        "busy_s_total": sum(busy),
        "window_s": window,
        "idle_pct": (1.0 - busy_s / window) * 100.0 if window > 0 else None,
        "breakdown": {"device_ops": [[k, v] for k, v in device_ops],
                      "idle_gaps": [[k, v] for k, v in idle_by_span]},
        "longest_gap_s": max((d for ds in by_gap.values() for d in ds),
                             default=0.0),
    }

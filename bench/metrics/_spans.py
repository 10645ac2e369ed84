"""Shared arithmetic of the readers of the program's own spans and
counters (`repro.obs`), not a metric itself.

The traced window's units are the last ``n`` spans of one name: studies
(``repro.study``, ``n`` the window's studies) or control ticks
(``repro.service.tick``, ``n`` the window's ticks); the warm-up's units
come before them. A record belongs to the unit it descends from by parent
links. A program without the recorder has no records: every reader then
returns None.
"""
import statistics

DISPATCH, DEVICE = "repro.sweep.dispatch", "repro.sweep.device"


def units(name: str, n: int):
    """``[(unit, [records under it])]`` for the last `n` spans called
    `name`, or None where the program records no such spans."""
    try:
        from repro import obs
    except ImportError:
        return None
    recs = obs.records()
    roots = [r for r in recs if r.name == name][-n:] if n else []
    if not roots or len(roots) < n:
        return None
    by_id = {r.id: r for r in recs}
    slot = {r.id: i for i, r in enumerate(roots)}
    under = [[] for _ in roots]
    for r in recs:
        p = r.parent
        while p is not None and p not in slot:
            up = by_id.get(p)
            p = None if up is None else up.parent
        if p is not None:
            under[slot[p]].append(r)
    return list(zip(roots, under))


def _dispatches(window):
    ds = [r for _, under in window or () for r in under if r.name == DISPATCH]
    if not ds or any("lane_events" not in d.counts
                     or "lane_steps_run" not in d.counts for d in ds):
        return None
    return ds


def lane_fill_pct(window):
    """Lane-events over lane-steps run, summed over the dispatches."""
    ds = _dispatches(window)
    if ds is None:
        return None
    steps = sum(d.counts["lane_steps_run"] for d in ds)
    events = sum(d.counts["lane_events"] for d in ds)
    return events / steps * 100.0 if steps > 0 else None


def device_ns_per_event(window):
    """Stamped device intervals, summed over chips, per lane-event."""
    ds = _dispatches(window)
    if ds is None:
        return None
    events = sum(d.counts["lane_events"] for d in ds)
    device = sum(r.t1 - r.t0 for _, under in window for r in under
                 if r.name == DEVICE)
    return device / events if events > 0 and device > 0 else None


def uncovered_ms(span, under) -> float:
    """The part of `span` that no device interval among `under` covers
    (the union over chips), in ms."""
    ivs = sorted((max(r.t0, span.t0), min(r.t1, span.t1)) for r in under
                 if r.name == DEVICE)
    covered, end = 0, span.t0
    for s, e in ivs:
        s = max(s, end)
        if e > s:
            covered += e - s
            end = e
    return (span.t1 - span.t0 - covered) * 1e-6


def median(values):
    return float(statistics.median(values)) if values else None

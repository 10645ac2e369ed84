"""Shared arithmetic of the per-layer readers (not a metric itself)."""


def busy_per_event_ns(run):
    """Device busy seconds, summed over chips, per lane-event, in ns."""
    tr, events = run.get("trace"), run.get("lane_events")
    if not tr or not events or tr["busy_s_total"] <= 0:
        return None
    return tr["busy_s_total"] * 1e9 / events


def idle_pct(run):
    tr = run.get("trace")
    if not tr or tr["busy_s_total"] <= 0:
        return None
    return tr["idle_pct"]

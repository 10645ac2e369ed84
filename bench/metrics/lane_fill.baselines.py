"""Lane fill of the rigid engine in the window's studies, in percent: the
events the dispatches moved on (`lane_events`, 2N per run) over the outer
trips they ran (`lane_steps_run`: runs x the trips of the longest run),
summed over the `repro.sweep.dispatch` spans of the window's
`repro.baselines` spans. What is missing is the cost of runs that have
drained while the longest still trips."""
import _spans


def read(run):
    return _spans.lane_fill_pct(
        _spans.units("repro.baselines", len(run["loop"].done)))

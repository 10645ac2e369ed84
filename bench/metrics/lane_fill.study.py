"""Lane fill of the device event loop in the window's studies, in percent:
the lane-events the dispatches moved on (the program's `lane_events`
counter, N + 2 x groups per real lane) over the lane-steps they ran
(`lane_steps_run`: padded lanes x segments of the longest lane x segment
length, per chip), summed over the `repro.sweep.dispatch` spans of the
window's `repro.study` spans."""
import _spans


def read(run):
    return _spans.lane_fill_pct(
        _spans.units("repro.study", len(run["loop"].done)))

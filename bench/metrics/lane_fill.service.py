"""Lane fill of the device event loop in the window's control ticks, in
percent: as `lane_fill.study`, over the `repro.sweep.dispatch` spans of the
window's `repro.service.tick` spans."""
import _spans


def read(run):
    n = sum(int(d["n_ticks"]) for d in run["loop"].done)
    return _spans.lane_fill_pct(_spans.units("repro.service.tick", n))

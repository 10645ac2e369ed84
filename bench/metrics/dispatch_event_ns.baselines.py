"""Device time per rigid event in the window's studies (ns/event): the
program's stamps of each scheduler's dispatch (`repro.sweep.device`),
summed over the dispatches of the window's `repro.baselines` spans, over
their `lane_events` (2N per run: each job submits and completes once)."""
import _spans


def read(run):
    return _spans.device_ns_per_event(
        _spans.units("repro.baselines", len(run["loop"].done)))

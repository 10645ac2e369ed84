"""Device time per lane-event in the window's studies (ns/event): the
program's stamps of each dispatch's device interval (`repro.sweep.device`,
from the later of its enqueue and the device's previous completion to its
own completion, one per chip), summed over chips, over the dispatches'
`lane_events`. Read from the program, so the device trace's buffer does
not cut it short."""
import _spans


def read(run):
    return _spans.device_ns_per_event(
        _spans.units("repro.study", len(run["loop"].done)))

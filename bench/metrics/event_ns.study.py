"""Device busy time per simulated lane-event (ns/event), from the profiler
trace: the union of device op intervals, summed over chips, over the
lane-events of the traced window (`reference.lane_events`)."""
import _device


def read(run):
    return _device.busy_per_event_ns(run)

"""The device event loop's share of its roofline, in percent: the least
time the traced lane-events need (`workmodel.least_seconds`: their
job-table lookups at HBM bandwidth, which bind over the flop term) over the
device busy time, summed over chips. Nothing is returned where the trace
shows no device time."""
import numpy as np


def read(run):
    tr, events = run.get("trace"), run.get("lane_events")
    if not tr or not events or tr["busy_s_total"] <= 0:
        return None
    cfg = run["cfg"]
    least, _ = run["workmodel"].least_seconds(
        events, cfg["flows"]["n_types"], np.dtype(cfg["dtype"]).itemsize,
        run["device_kind"])
    return least / tr["busy_s_total"] * 100.0

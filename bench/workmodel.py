"""Device peaks and the work of one lane-event, for roofline shares.

Peaks are published figures, keyed by JAX's `device_kind`; a device that is
not listed is an error. TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16, 16 GB of
HBM at 819 GB/s (Google Cloud documentation, "TPU v5e",
https://cloud.google.com/tpu/docs/v5e).

A lane-event is one step of one experiment: a submission consumed, a group
formed or a group completed (`reference.lane_events` counts them from a
lane's own outputs). Whatever the implementation, each event looks up the
flow's [N]-indexed job tables: per job type the queued-work prefix at head
and tail (2H reads), and the submit time, the type of the next submission
and a few more picks (6 reads), at the simulation's width. That is the only
traffic every implementation must pay; state that a resident kernel keeps
on the chip is not counted. The float work is a handful of operations per
job type for the queue weights and a fixed amount of group arithmetic.
The least time of an event is the larger of bytes / HBM bandwidth and
flops / peak; at these counts the bytes term binds.
"""
from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9},
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}") from None


def event_work(n_types: int, dtype_bytes: int) -> dict:
    """Bytes and flops one lane-event needs at least."""
    return {"bytes": (2 * int(n_types) + 6) * int(dtype_bytes),
            "flops": 14 * int(n_types) + 48}


def least_seconds(lane_events: int, n_types: int, dtype_bytes: int,
                  device_kind: str) -> tuple[float, str]:
    """The least device time `lane_events` events need, and which term
    binds it ("bytes" or "flops")."""
    peaks = device_peaks(device_kind)
    w = event_work(n_types, dtype_bytes)
    t_bytes = lane_events * w["bytes"] / peaks["hbm_bw"]
    t_flops = lane_events * w["flops"] / peaks["peak_flops"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")

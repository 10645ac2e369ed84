"""What decides `correct`: the numbers that compare the timed path's
answers with the plain reference (`reference.simulate`). Each loop kind's
`check` draws its sample from the seed and hands the pairs here; a cell's
`limits/<cell>.json` names which of the numbers it compares, and the others
are printed only.

Study cells, over sampled experiments (each a flow, k and init proportion
simulated again by the reference):

* ``worst_gap``: the largest relative gap |program - reference| /
  max(|reference|, floor) over the sampled experiments and the paper's six
  metrics (average, median and run-start wait, queue length, full and useful
  utilisation);
* ``wait_gap``: the same over the average wait alone, the metric whose
  curve over k the tuning reads;
* ``lanes_off_pct``: the share, in percent, of sampled experiments whose
  largest gap exceeds ``LANE_OFF_GAP`` (further off than the configuration's
  precision takes an experiment: another experiment's answer, say);
* ``groups_off_pct``: the share of sampled experiments whose group count
  differs from the reference's.

Service cells, over whole scenario runs (every tick's tuning curve: the
average wait per candidate k that the program's oracle handed to its
controllers, and each controller's committed k):

* ``curve_off_pct``: the share, in percent, of (tick, k) points whose
  relative gap exceeds ``CURVE_OFF_GAP``;
* ``curve_gap``: the largest of those gaps;
* ``k_off_pct``: the share of (tick, controller) commitments that differ
  from the reference's replay.

The lower-precision control is the same reference computed with every
result rounded to the next precision below the configuration's
(`reference.rounder`), put in the program's place.
"""
from __future__ import annotations

import numpy as np

STUDY_FIELDS = ("avg_wait", "med_wait", "avg_qlen", "full_util",
                "useful_util", "avg_run_wait")
FLOORS = {"avg_wait": 1e-3, "med_wait": 1e-3, "avg_run_wait": 1e-3,
          "avg_qlen": 1e-6, "full_util": 1e-6, "useful_util": 1e-6}
#: an experiment off by more than this is off by more than float32 moves a
#: whole study's experiment: nine in ten of those read under 0.02 (runs on
#: a TPU v5e, PERF.md)
LANE_OFF_GAP = 0.05
#: a point of a tick's curve off by more than this is off by more than
#: float32 rounding of one 400-job window, which reads about 1e-6
CURVE_OFF_GAP = 1e-4


def rel_gap(got: float, want: float, floor: float) -> float:
    if not np.isfinite(got):
        return float("inf")
    return abs(got - want) / max(abs(want), floor)


def lane_gaps(pairs) -> list:
    """`pairs`: [(program answer, reference answer)] of single experiments;
    the largest relative gap of each over the six metrics."""
    return [max(rel_gap(got[f], want[f], fl) for f, fl in FLOORS.items())
            for got, want in pairs]


def lane_numbers(pairs) -> dict:
    gaps = lane_gaps(pairs)
    off = sum(int(int(got["n_groups"]) != int(want["n_groups"]))
              for got, want in pairs)
    n = max(len(pairs), 1)
    wait = [rel_gap(got["avg_wait"], want["avg_wait"], FLOORS["avg_wait"])
            for got, want in pairs]
    return {"worst_gap": max(gaps, default=float("inf")),
            "wait_gap": max(wait, default=float("inf")),
            "lanes_off_pct": 100.0 * sum(g > LANE_OFF_GAP for g in gaps)
            / n,
            "groups_off_pct": 100.0 * off / n}


def tick_gaps(runs) -> list | None:
    """`runs`: [(program curves, program commitments, reference curves,
    reference commitments)] of whole scenario runs; the relative gap of
    every (tick, k) point, or None where a run's ticks do not line up."""
    gaps = []
    for curves, _, ref_curves, _ in runs:
        if len(curves) != len(ref_curves):
            return None
        for got, want in zip(curves, ref_curves):
            if len(got) != len(want):
                return None
            gaps.extend(rel_gap(float(g), float(w), FLOORS["avg_wait"])
                        for g, w in zip(got, want))
    return gaps or None


def tick_numbers(runs) -> dict:
    inf = {"curve_off_pct": 100.0, "curve_gap": float("inf"),
           "k_off_pct": 100.0}
    gaps = tick_gaps(runs)
    if gaps is None:
        return inf
    off, n = 0, 0
    for _, committed, _, ref_committed in runs:
        for name, ks in ref_committed.items():
            got = committed.get(name, [])
            if len(got) != len(ks):
                return inf
            off += sum(int(a != b) for a, b in zip(got, ks))
            n += len(ks)
    return {"curve_off_pct": 100.0 * sum(g > CURVE_OFF_GAP for g in gaps)
            / len(gaps),
            "curve_gap": max(gaps),
            "k_off_pct": 100.0 * off / max(n, 1)}

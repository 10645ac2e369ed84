"""Loop kind ``baselines``: whole rigid studies, back to back.

One study is one `repro.core.run_cohort_baselines` call over the
configuration's flows: every (flow, scheduler, init proportion) run, 36 for
3 flows, FCFS and EASY backfill and 6 init proportions. Study i's flows
come from ``(seed, i)``: rigid schedulers ignore job types, so the study
loop's permutation of type labels would give every seed the same work.
Only whole studies count: the study in flight when the window's time is up
is finished, and the window ends with it.

`check` compares runs of the window's first study, drawn from the seed
alone (so that a faster program compares the same runs as its parent),
with the plain reference (`reference_rigid`) rounded to the
configuration's precision:

* ``starts_off_pct``: the share, in percent, of the sampled runs' jobs
  whose start time differs from the reference's;
* ``worst_gap``: the largest relative gap over the sampled runs and the
  paper's six metrics (`check.lane_gaps`).

``worst_gap_f64``, the same gap against the float64 reference, is printed
only.
"""
from __future__ import annotations

import time

import numpy as np

import check
import gen
import reference
import reference_rigid
from _common import program_workload, span

FIELDS = check.STUDY_FIELDS


def backfilled(start) -> int:
    """Jobs that started before a job submitted ahead of them."""
    start = np.asarray(start, np.float64)
    return int((start[1:] < np.maximum.accumulate(start)[:-1]).sum())


class BaselinesLoop:
    """Whole rigid studies, back to back."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro import core
        if not hasattr(core, "run_cohort_baselines"):
            raise RuntimeError("the program has no rigid cohort path "
                               "(repro.core.run_cohort_baselines)")
        self.core, self.cfg, self.seed = core, cfg, seed
        self.s_props = tuple(cfg["init_props"])
        self.schedulers = tuple(cfg["schedulers"])
        self.depth = int(cfg["backfill_depth"])
        self.dtype = np.dtype(cfg["dtype"])
        self.done = []          # (flows, {name: {scheduler: {field: [S]}}})
        self.rings = set()

    def flows(self, index: int) -> dict:
        f = self.cfg["flows"]
        return {f"{f['prefix']}{load:.2f}": gen.generate(
                    n_jobs=f["n_jobs"], horizon=f["horizon_s"],
                    n_types=f["n_types"], nodes=f["nodes"], load=load,
                    homogeneous=f["homogeneous"],
                    daily_amplitude=f["daily_amplitude"],
                    homog_shrink=f["homog_shrink"],
                    seed=gen.seed_int(self.seed, index, i))
                for i, load in enumerate(f["loads"])}

    def study(self, index: int):
        core = self.core
        with span("generate"):
            flows = self.flows(index)
            wls = {n: program_workload(fl) for n, fl in flows.items()}
        with span("pack"):
            cohorts = core.group_workloads(wls, {n: self.dtype for n in wls})
            if len(cohorts) != 1:
                raise RuntimeError(f"the flows form {len(cohorts)} cohorts")
            cohort = cohorts[0]
            cohort.pack()
            self.rings.add(int(cohort.ring))
        with span("run_cohort_baselines"):
            runs = core.run_cohort_baselines(
                cohort, s_props=self.s_props, schedulers=self.schedulers,
                backfill_depth=self.depth, on_budget_exhausted="ignore")
        with span("unstack"):
            res = {n: {sched: dict(
                       {f: np.asarray(getattr(run.metrics, f))
                        for f in FIELDS + ("ok", "budget_exhausted")},
                       start_t=np.asarray(run.start_t))
                       for sched, run in runs[n].items()}
                   for n in flows}
        return flows, res

    def warm_up(self):
        self.study(-1)
        if self.rings != {int(self.cfg["ring"])}:
            raise RuntimeError(f"the program ran rings {self.rings}, the "
                               f"configuration states {self.cfg['ring']}")
        self.plan = {"layout": "one program per scheduler",
                     "schedulers": list(self.schedulers),
                     "lanes": len(self.cfg["flows"]["loads"])
                     * len(self.s_props),
                     "backfill_depth": self.depth,
                     "ring": sorted(self.rings)}

    def run(self, seconds: float, units: int | None = None) -> float:
        """The window: returns its seconds; `units` (if set) ends the
        window after that many studies instead."""
        t0 = time.perf_counter()
        i = 0
        while True:
            self.done.append(self.study(i))
            i += 1
            el = time.perf_counter() - t0
            if units is not None:
                if i >= units:
                    return el
            elif el >= seconds:
                return el

    def _runs(self):
        return [run for _, r in self.done for by in r.values()
                for run in by.values()]

    def counts(self) -> dict:
        runs = self._runs()
        return {"attempted": sum(run["ok"].size for run in runs),
                "failed": sum(int((~run["ok"].astype(bool)
                                   | run["budget_exhausted"].astype(bool))
                                  .sum()) for run in runs),
                "units": len(self.done)}

    def lane_events(self, info: dict) -> int:
        """2N per run: each job is submitted and completes once."""
        return 2 * self.cfg["flows"]["n_jobs"] * self.counts()["attempted"]

    def end_to_end(self, window_s: float) -> dict:
        return {"experiments_per_s": self.counts()["attempted"] / window_s}

    # ------------------------------------------------------------ checking
    def sample(self, rng, n: int):
        """(flow, scheduler, i_s) runs of the first study to check: `n`
        drawn from the seed, plus the EASY run that backfilled most."""
        res = self.done[0][1]
        pos = [(name, sched, j) for name in res for sched in res[name]
               for j in range(len(self.s_props))]
        pick = rng.choice(len(pos), size=min(n, len(pos)), replace=False)
        out = [pos[int(p)] for p in pick]
        easy = [q for q in pos if q[1] == "backfill"]
        if easy:
            most = max(easy, key=lambda q: backfilled(
                res[q[0]][q[1]]["start_t"][q[2]]))
            if most not in out:
                out.append(most)
        return out

    def reference_run(self, where, rnd=None) -> dict:
        name, sched, j = where
        fl = self.done[0][0][name]
        s = reference.init_time(fl["runtime"], self.s_props[j])
        return reference_rigid.simulate(
            fl["submit"], fl["runtime"], fl["nodes"], fl["nodes_total"], s,
            sched, depth=self.depth, slots=self.cfg["ring"], rnd=rnd)

    def program_run(self, where) -> dict:
        name, sched, j = where
        r = self.done[0][1][name][sched]
        return dict({f: float(r[f][j]) for f in FIELDS},
                    start=np.asarray(r["start_t"][j], np.float64))

    @staticmethod
    def numbers(pairs) -> dict:
        off = sum(int((got["start"] != want["start"]).sum())
                  for got, want in pairs)
        jobs = sum(want["start"].size for _, want in pairs)
        return {"starts_off_pct": 100.0 * off / max(jobs, 1),
                "worst_gap": max(check.lane_gaps(pairs),
                                 default=float("inf"))}

    def check(self, rng, n: int, rnd=None) -> tuple[dict, dict]:
        """Compared numbers, and what was checked. With `rnd`, the
        reference rounded by it stands in for the program (the
        lower-precision control)."""
        r_cfg = reference.rounder(self.cfg["dtype"])
        pairs, pairs64, steps = [], [], 0
        for w in self.sample(rng, n):
            want = self.reference_run(w, rnd=r_cfg)
            want64 = self.reference_run(w)
            steps += want["steps"] + want64["steps"]
            got = (self.program_run(w) if rnd is None
                   else self.reference_run(w, rnd=rnd))
            pairs.append((got, want))
            pairs64.append((got, want64))
        numbers = dict(self.numbers(pairs), worst_gap_f64=max(
            check.lane_gaps(pairs64), default=float("inf")))
        strip = lambda d: {f: d[f] for f in FIELDS}
        return numbers, {
            "checked": len(pairs), "steps": steps,
            "gaps": check.lane_gaps(pairs),
            "pairs": [(strip(g), strip(w)) for g, w in pairs]}


LOOP = BaselinesLoop

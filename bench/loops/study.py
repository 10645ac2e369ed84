"""Loop kind ``study``: whole paper studies, back to back.

One study is one `repro.core.run_cohort_grid` call over the
configuration's flows (every (flow, k, init proportion) experiment). Study
i's job sizes and arrivals come from i, the job types' labels from
``(seed, i)``. Only whole studies count: the study in flight when the
window's time is up is finished, and the window ends with it.

The loop keeps what the window produced; `check` compares a sample of it,
drawn from the seed, with the plain reference once the window has closed.
"""
from __future__ import annotations

import time

import numpy as np

import check
import gen
import reference
from _common import program_workload, simulate, span

FIELDS = check.STUDY_FIELDS


class StudyLoop:
    """Whole paper studies, back to back."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro import core
        self.core, self.cfg, self.mix, self.seed = core, cfg, mix, seed
        self.ks = tuple(cfg["scale_ratios"])
        self.s_props = tuple(cfg["init_props"])
        self.dtype = np.dtype(cfg["dtype"])
        self.done = []          # (flows, {name: {field: [K, S]}}) per study
        self.rings = set()      # group rings the studies' cohorts ran with

    def flows(self, index: int) -> dict:
        """Study `index`'s flows. Job sizes and arrivals come from the
        study index alone, so every seed does the same amount of work; the
        run's seed permutes the job types' labels of each flow, so that no
        two seeds send the same flows."""
        f = self.cfg["flows"]
        out = {}
        for i, load in enumerate(f["loads"]):
            fl = gen.generate(
                n_jobs=f["n_jobs"], horizon=f["horizon_s"],
                n_types=f["n_types"], nodes=f["nodes"], load=load,
                homogeneous=f["homogeneous"],
                daily_amplitude=f["daily_amplitude"],
                homog_shrink=f["homog_shrink"], seed=gen.seed_int(index, i))
            perm = np.random.default_rng(gen.seed_int(self.seed, index, i)
                                         ).permutation(f["n_types"])
            fl["jtype"] = perm[fl["jtype"]]
            out[f"{f['prefix']}{load:.2f}"] = fl
        return out

    def study(self, index: int):
        core = self.core
        with span("generate"):
            flows = self.flows(index)
            wls = {n: program_workload(fl) for n, fl in flows.items()}
        with span("pack"):
            cohorts = core.group_workloads(
                wls, {n: self.dtype for n in wls})
            if len(cohorts) != 1:
                raise RuntimeError(f"the flows form {len(cohorts)} cohorts")
            cohort = cohorts[0]
            cohort.pack()
            self.rings.add(int(cohort.ring))
        with span("run_cohort_grid"):
            grids = core.run_cohort_grid(
                cohort, ks=self.ks, s_props=self.s_props,
                mode=self.cfg["layout"], step_impl=self.cfg["step_impl"],
                on_budget_exhausted="ignore")
        with span("unstack"):
            res = {n: {f: np.asarray(getattr(grids[n], f))
                       for f in FIELDS + ("n_groups", "ok",
                                          "budget_exhausted")}
                   for n in flows}
        return flows, res

    def warm_up(self):
        self.study(-1)
        if self.rings != {int(self.cfg["ring"])}:
            raise RuntimeError(f"the program ran group rings {self.rings}, "
                               f"the configuration states {self.cfg['ring']}")
        self.plan = dict(self.core.sweep_plan(
            self.cfg["layout"], len(self.ks) * len(self.s_props),
            len(self.cfg["flows"]["loads"]),
            step_impl=self.cfg["step_impl"]), ring=sorted(self.rings))

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

    def counts(self) -> dict:
        lanes = sum(r[n]["ok"].size for _, r in self.done for n in r)
        failed = sum(int((~r[n]["ok"].astype(bool)
                          | r[n]["budget_exhausted"].astype(bool)).sum())
                     for _, r in self.done for n in r)
        return {"attempted": lanes, "failed": failed,
                "units": len(self.done)}

    def lane_events(self, info: dict) -> int:
        """Lane-events of the window, from each lane's own outputs."""
        n_jobs = self.cfg["flows"]["n_jobs"]
        return sum(int(reference.lane_events(n_jobs, r[n]["n_groups"]).sum())
                   for _, r in self.done for n in r)

    def end_to_end(self, window_s: float) -> dict:
        return {"experiments_per_s": self.counts()["attempted"] / window_s}

    # ------------------------------------------------------------ checking
    def sample(self, rng, n: int):
        """(study, flow, i_k, i_s) positions to check: `n` drawn from the
        seed, plus the position with the most events in the window."""
        pos = [(u, name, i, j) for u, (_, r) in enumerate(self.done)
               for name in r for i in range(len(self.ks))
               for j in range(len(self.s_props))]
        pick = rng.choice(len(pos), size=min(n, len(pos)), replace=False)
        out = [pos[int(p)] for p in pick]
        longest = max(pos, key=lambda q: int(
            self.done[q[0]][1][q[1]]["n_groups"][q[2], q[3]]))
        if longest not in out:
            out.append(longest)
        return out

    def reference_lane(self, where, rnd=None) -> dict:
        u, name, i, j = where
        fl = self.done[u][0][name]
        s = reference.init_time(fl["runtime"], self.s_props[j])
        return simulate(self.cfg, fl["submit"], fl["work"], fl["jtype"],
                        fl["n_types"], self.ks[i], s, rnd=rnd)

    def program_lane(self, where) -> dict:
        u, name, i, j = where
        r = self.done[u][1][name]
        return {f: float(r[f][i, j]) for f in FIELDS + ("n_groups",)}

    def check(self, rng, n: int, rnd=None) -> tuple[dict, dict]:
        """Compared numbers (`check.lane_numbers`), and what was checked.
        With `rnd`, the reference rounded by it stands in for the program
        (the lower-precision control)."""
        pairs, steps = [], 0
        for w in self.sample(rng, n):
            want = self.reference_lane(w)
            steps += want["steps"]
            got = (self.program_lane(w) if rnd is None
                   else self.reference_lane(w, rnd=rnd))
            pairs.append((got, want))
        return check.lane_numbers(pairs), {
            "checked": len(pairs), "steps": steps,
            "gaps": check.lane_gaps(pairs), "pairs": pairs}


LOOP = StudyLoop

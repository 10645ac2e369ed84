"""Drive whole benchmark runs with the timed path broken underneath.

    python3 bench/tests/faulty_run.py <fault>[,<fault>...] -- <run_cell args>

For each fault (``none`` is the unbroken run) the program's entry point
that the cell's window drives (`repro.core.run_cohort_grid` or
`repro.service.run_service`) is wrapped so that what it returns is wrong in
that way; the rest of the run is the harness's own (`run_cell.run`, without
its look for a chip). One JSON line per fault: ``{"fault", "correct",
"failed", "checks"}``.

Faults, as they would arise in the program:

* ``state_unchanged``: the event step returns its state unchanged, so no
  lane schedules a job (every lane out of budget) or every tick degrades;
* ``half_batch``: half of the batch is left out and filled with the mean
  taken over the rest (lanes of a study; candidate k of a tick's curve);
* ``answer_altered``: an answer is altered where it is produced: the
  average wait of each flow's longest experiment becomes twice itself plus
  a second; each tick's tuning curve is read one candidate k off;
* ``no_exchange``: the lanes of every chip but the first are never
  gathered, and the first chip's lanes stand in for them;
* ``lane_shift``: each flow's results land one k off (its [K, S] grid
  rolled by one along k);
* ``chunk_swap``: two neighbouring chunks of the one-chip layout come back
  in each other's places: lanes sorted by ascending k * s (most events
  first), as the chunked layout orders them, cut into chunks of at most
  64, the second and third chunk swapped;
* ``curve_raised``: the last third of each tick's tuning curve (the
  largest k) is read 20% high.
"""
import json
import sys

import _paths  # noqa: F401
import numpy as np

import check
import run_cell

STUDY_FIELDS = check.STUDY_FIELDS


def _chunk_swap_order(ks, s_props, chunk=64):
    """Flat [K, S] positions, permuted so that two neighbouring chunks of
    the sorted lanes trade places."""
    k = np.repeat(np.asarray(ks, np.float64), len(s_props))
    sp = np.asarray(s_props, np.float64)
    s = np.tile(sp / (1.0 - sp), len(ks))
    order = np.argsort(k * s, kind="stable")
    L = len(order)
    width = -(-L // -(-L // chunk))
    a, b = order[width:2 * width], order[2 * width:3 * width]
    n = min(len(a), len(b))
    perm = np.arange(L)
    perm[a[:n]], perm[b[:n]] = b[:n], a[:n]
    return perm


def _study_fault(fault, grids, ks, s_props):
    out = {}
    for name, m in grids.items():
        d = {f: np.array(getattr(m, f)) for f in m._fields}
        if fault == "lane_shift":
            d = {f: np.roll(v, 1, axis=0) for f, v in d.items()}
        elif fault == "chunk_swap":
            perm = _chunk_swap_order(ks, s_props)
            d = {f: v.reshape(-1)[perm].reshape(v.shape)
                 for f, v in d.items()}
        flat = {f: v.reshape(-1) for f, v in d.items()}
        L = flat["avg_wait"].size
        if fault == "state_unchanged":
            for f in STUDY_FIELDS:
                flat[f][:] = np.inf
            flat["n_groups"][:] = 0
            flat["ok"][:] = False
            flat["budget_exhausted"][:] = True
        elif fault == "half_batch":
            for f in STUDY_FIELDS:
                flat[f][L // 2:] = flat[f][:L // 2].mean()
        elif fault == "answer_altered":
            i = int(np.argmax(flat["n_groups"]))
            flat["avg_wait"][i] = 2.0 * flat["avg_wait"][i] + 1.0
        elif fault == "no_exchange":
            q = -(-L // 4)
            for f in STUDY_FIELDS + ("n_groups",):
                flat[f][q:] = np.resize(flat[f][:q], L - q)
        out[name] = m._replace(**{f: flat[f].reshape(d[f].shape)
                                  for f in d})
    return out


class _AlterCurve:
    def __init__(self, inner, fault, state):
        self._inner, self._fault, self._state = inner, fault, state
        self.name = inner.name

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def decide(self, ks, avg_wait, *a, **kw):
        w = np.array(avg_wait, np.float64)
        tick = self._state.setdefault(self.name, 0)
        self._state[self.name] = tick + 1
        if self._fault == "half_batch":
            w[len(w) // 2:] = w[:len(w) // 2].mean()
        elif self._fault == "answer_altered":
            w = np.roll(w, 1)
        elif self._fault == "curve_raised":
            w[len(w) - len(w) // 3:] *= 1.2
        return self._inner.decide(ks, w, *a, **kw)


def install(fault):
    """Wrap the program's entry points for `fault`; returns an undo."""
    from repro import core, service
    real_grid, real_service = core.run_cohort_grid, service.run_service

    def grid(cohort, ks, s_props, **kw):
        return _study_fault(fault, real_grid(cohort, ks=ks, s_props=s_props,
                                             **kw), ks, s_props)

    def run_service(wl, config, controllers=None, **kw):
        state = {}
        ctls = [_AlterCurve(c, fault, state) for c in controllers]
        out = real_service(wl, config, controllers=ctls, **kw)
        if fault == "state_unchanged":
            for t in out["ticks"]:
                t["degraded"] = True
            out["n_degraded_ticks"] = out["n_ticks"]
        return out

    if fault != "none":
        core.run_cohort_grid, service.run_service = grid, run_service

    def undo():
        core.run_cohort_grid, service.run_service = real_grid, real_service
    return undo


def main(argv):
    faults, args = argv[0].split(","), argv[argv.index("--") + 1:]
    for fault in faults:
        undo = install(fault)
        try:
            out, checks = run_cell.run(run_cell.parse_args(
                args + ["--allow-cpu"]))
        finally:
            undo()
        print(json.dumps({"fault": fault, "correct": out["correct"],
                          "failed": out["failed"], "checks": checks}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])

"""Loop kind ``service``: control ticks of `repro.service.run_service`.

The drift scenarios are drawn fresh from ``(seed, cycle)`` and cycled
until the window's time is up (the scenario in flight is finished). Each
tick is timed from outside the program: a wrapper of the first controller
stamps the clock when the tick reaches its decision, and a scenario's
first tick is timed from the start of the call.

The loop keeps what the window produced; `check` compares whole scenario
runs, drawn from the seed, with the plain reference once the window has
closed: the reference simulates every tick's window for every candidate k
and replays the two controllers' rules on its own curves.
"""
from __future__ import annotations

import time

import numpy as np

import check
import gen
import reference
from _common import program_workload, simulate


class _Stamped:
    """A controller that stamps the clock, and keeps the curve it is handed,
    each time its tick reaches the decision."""

    def __init__(self, inner, loop):
        self._inner, self._loop = inner, loop
        self.name = inner.name

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def decide(self, ks, avg_wait, *a, **kw):
        self._loop._tick_done(np.array(avg_wait, np.float64))
        return self._inner.decide(ks, avg_wait, *a, **kw)


class ServiceLoop:
    """Control ticks of the streaming service over drift scenarios."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro import service
        self.service, self.cfg, self.mix, self.seed = service, cfg, mix, seed
        self.ks = tuple(cfg["scale_ratios"])
        self.done = []          # one record per scenario run
        self._ann = None

    def scenarios(self, cycle: int) -> dict:
        m = self.mix
        return gen.drift_scenarios(
            n_jobs=m["scenario_jobs"], nodes=self.cfg["flows"]["nodes"],
            seed=gen.seed_int(self.seed, cycle),
            n_segments=m["scenario_segments"])

    def _open_tick(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation("bench.tick")
        self._ann.__enter__()

    def _close_tick(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def _tick_done(self, curve):
        self._stamps.append(time.perf_counter())
        self._curves.append(curve)
        self._close_tick()
        self._open_tick()

    def scenario(self, flow: dict) -> dict:
        m = self.mix
        config = self.service.ServiceConfig(
            ks=self.ks, s_prop=m["s_prop"], window_jobs=m["window_jobs"],
            stride_jobs=m["stride_jobs"], dtype=self.cfg["dtype"],
            mode=self.cfg["layout"], on_budget_exhausted="degrade")
        ctls = self.service.default_controllers(config)
        ctls = [_Stamped(ctls[0], self)] + ctls[1:]
        wl = program_workload(flow)
        self._stamps, self._curves = [], []
        t0 = time.perf_counter()
        self._open_tick()
        try:
            out = self.service.run_service(wl, config, controllers=ctls)
        finally:
            self._close_tick()
        stamps = np.asarray(self._stamps)
        tick_ms = np.diff(np.concatenate([[t0], stamps])) * 1e3
        healthy = [t for t in out["ticks"] if not t.get("degraded")]
        return {
            "flow": flow, "tick_ms": tick_ms,
            "oracle_ms": np.asarray([t["oracle_ms"] for t in healthy]),
            "curves": self._curves,
            "committed": {c.name: [t["controllers"][c.name]["committed_k"]
                                   for t in healthy] for c in ctls},
            "n_ticks": out["n_ticks"],
            "degraded": int(out.get("n_degraded_ticks", 0)),
        }

    def warm_up(self):
        flow = next(iter(self.scenarios(-1).values()))
        self.scenario(flow)
        self.plan = {"mode": self.cfg["layout"], "ks": len(self.ks)}

    def run(self, seconds: float, units: int | None = None) -> float:
        """The window: returns its seconds; `units` (if set) ends the
        window after that many scenario runs instead."""
        t0 = time.perf_counter()
        cycle = 0
        while True:
            for flow in self.scenarios(cycle).values():
                self.done.append(self.scenario(flow))
                el = time.perf_counter() - t0
                if units is not None:
                    if len(self.done) >= units:
                        return el
                elif el >= seconds:
                    return el
            cycle += 1

    def ticks(self):
        return np.concatenate([d["tick_ms"] for d in self.done])

    def counts(self) -> dict:
        return {"attempted": int(sum(d["n_ticks"] for d in self.done)),
                "failed": int(sum(d["degraded"] for d in self.done)),
                "units": len(self.done)}

    def lane_events(self, info: dict) -> int | None:
        """Lane-events of the window: the reference's steps over the same
        windows, where the check covered every scenario run."""
        return info["steps"] if info["all_units"] else None

    def end_to_end(self, window_s: float) -> dict:
        t = self.ticks()
        return {"tick_p50_ms": float(np.percentile(t, 50)),
                "tick_p95_ms": float(np.percentile(t, 95))}

    # ------------------------------------------------------------ checking
    def bounds(self, n_jobs: int):
        w, st = self.mix["window_jobs"], self.mix["stride_jobs"]
        return [(lo, lo + w) for lo in range(0, n_jobs - w + 1, st)]

    def reference_ticks(self, rec: dict, rnd=None) -> list:
        """The reference's curve ([K] avg_wait) and lane steps per tick."""
        fl, out = rec["flow"], []
        for lo, hi in self.bounds(len(fl["submit"])):
            sub = fl["submit"][lo:hi] - fl["submit"][lo]
            s = reference.init_time(fl["runtime"][lo:hi], self.mix["s_prop"])
            lanes = [simulate(self.cfg, sub, fl["work"][lo:hi],
                              fl["jtype"][lo:hi], fl["n_types"], k, s,
                              rnd=rnd)
                     for k in self.ks]
            out.append({"curve": np.array([x["avg_wait"] for x in lanes]),
                        "steps": sum(x["steps"] for x in lanes)})
        return out

    def replay(self, curves) -> dict:
        """Each controller's committed k per tick, by the rules the paper's
        plateau reading gives (hysteresis) and the arg-best foil (naive)."""
        ks = np.asarray(self.ks)
        rel, arel = self.mix["plateau_rel_tol"], self.mix["plateau_abs_rtol"]
        held, hyst, naive = None, [], []
        for w in curves:
            i_best = int(np.argmin(w))
            best = float(w[i_best])
            tol = rel * max(best, 1.0) + arel * max(best, 1.0)
            if held is None or float(w[int(np.flatnonzero(ks == held)[0])]) \
                    > best + tol:
                held = float(ks[i_best])
            hyst.append(held)
            naive.append(float(ks[i_best]))
        return {"hysteresis": hyst, "naive": naive}

    def check(self, rng, n: int, rnd=None) -> tuple[dict, dict]:
        """Compared numbers (`check.tick_numbers`) over `n` whole scenario
        runs drawn from the seed, and what was checked. With `rnd`, the
        reference rounded by it stands in for the program (the
        lower-precision control)."""
        pick = rng.choice(len(self.done), size=min(n, len(self.done)),
                          replace=False)
        runs, steps = [], 0
        for i in sorted(int(p) for p in pick):
            rec = self.done[i]
            ref = self.reference_ticks(rec)
            steps += sum(t["steps"] for t in ref)
            ref_curves = [t["curve"] for t in ref]
            if rnd is None:
                curves, committed = rec["curves"], rec["committed"]
            else:
                curves = [t["curve"]
                          for t in self.reference_ticks(rec, rnd=rnd)]
                committed = self.replay(curves)
            runs.append((curves, committed, ref_curves,
                         self.replay(ref_curves)))
        return check.tick_numbers(runs), {"checked": len(runs), "steps": steps,
                         "all_units": len(runs) == len(self.done),
                         "gaps": check.tick_gaps(runs)}


LOOP = ServiceLoop

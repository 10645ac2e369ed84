"""Traffic generators of the benchmark: the Lublin–Feitelson job model.

A copy of the program's generator (`repro.workload.lublin.generate_workload`
and `repro.workload.windows.drift_scenarios`, as they stood when the
benchmark was defined), kept here so that a later change to the program
cannot move the yardstick. Lublin & Feitelson, "The Workload on Parallel
Supercomputers: Modeling the Characteristics of Rigid Jobs", JPDC 2003,
with the paper's "more homogeneous" variant and load calibration
(arXiv:2311.17889 §6). `tests/test_gen.py` pins the copy to digests of the
program's flows recorded in `golden_digests.json`.

A flow is a plain dict of numpy arrays sorted by submit time:
``submit, runtime, nodes, work, jtype`` plus ``nodes_total`` (M),
``n_types`` and ``params``.
"""
from __future__ import annotations

import hashlib

import numpy as np

DAY = 86400.0

# Lublin's published "batch" model constants.
SERIAL_PROB = 0.244
POW2_PROB = 0.75
ULOW = 0.8
UPROB = 0.86
A1, B1 = 4.2, 0.94
A2, B2 = 312.0, 0.03
PA, PB = -0.0054, 0.78
AARR, BARR = 10.23, 0.4871

DEFAULTS = dict(n_jobs=5000, horizon=4 * DAY, n_types=8, nodes=500,
                load=0.85, homogeneous=False, seed=0, daily_amplitude=0.6,
                homog_shrink=0.25)


def _hyper_gamma_ln_runtime(rng, log2n):
    p = np.clip(PA * log2n + PB, 0.01, 0.99)
    pick1 = rng.random(log2n.shape) < p
    g1 = rng.gamma(A1, B1, size=log2n.shape)
    g2 = rng.gamma(A2, B2, size=log2n.shape)
    return np.where(pick1, g1, g2)


def _node_counts(rng, n, max_nodes, homogeneous):
    uhi = np.log2(max_nodes)
    umed = (uhi - ULOW) * 0.625 + ULOW
    if homogeneous:
        u = rng.uniform(3.0, 5.0, size=n)
        return np.clip(np.round(2.0 ** u), 1, max_nodes).astype(np.int64)
    serial = rng.random(n) < SERIAL_PROB
    low = rng.random(n) < UPROB
    u = np.where(low, rng.uniform(ULOW, umed, size=n),
                 rng.uniform(umed, uhi, size=n))
    pow2 = rng.random(n) < POW2_PROB
    size = np.where(pow2, np.round(u), u)
    nodes = np.clip(np.round(2.0 ** size), 1, max_nodes).astype(np.int64)
    return np.where(serial, 1, nodes)


def _arrivals(rng, n, horizon, amplitude):
    ln_gap = rng.gamma(AARR, BARR, size=n)
    gaps = np.exp(ln_gap - ln_gap.mean(axis=-1, keepdims=True))
    t = np.cumsum(gaps, axis=-1)
    t = t / t[..., -1:] * horizon
    peak = 0.58 * DAY
    phase = 2 * np.pi * (t - peak) / DAY
    warped = t - amplitude * DAY / (2 * np.pi) * np.sin(phase)
    warped = np.sort(warped - warped.min(axis=-1, keepdims=True), axis=-1)
    return warped / np.maximum(warped[..., -1:], 1e-9) * horizon


def generate(**kw) -> dict:
    """One flow; keyword arguments override `DEFAULTS`. `seed` may be an int
    or a sequence of ints (numpy's seeding of `default_rng`)."""
    p = {**DEFAULTS, **kw}
    rng = np.random.default_rng(p["seed"])
    n = p["n_jobs"]
    nodes = _node_counts(rng, n, p["nodes"], p["homogeneous"])
    ln_rt = _hyper_gamma_ln_runtime(rng, np.log2(nodes.astype(np.float64)))
    if p["homogeneous"]:
        ln_rt = ln_rt.mean() + (ln_rt - ln_rt.mean()) * p["homog_shrink"]
    runtime = np.clip(np.exp(ln_rt), 1.0, 2 * DAY)
    submit = _arrivals(rng, n, p["horizon"], p["daily_amplitude"])
    type_weights = 1.0 / np.arange(1, p["n_types"] + 1)
    type_weights /= type_weights.sum()
    jtype = rng.choice(p["n_types"], size=n, p=type_weights).astype(np.int64)
    raw_load = (runtime * nodes).sum() / (p["nodes"] * p["horizon"])
    runtime = runtime * (p["load"] / raw_load)
    order = np.argsort(submit, kind="stable")
    submit, runtime, nodes, jtype = (a[order] for a in
                                     (submit, runtime, nodes, jtype))
    return dict(submit=submit, runtime=runtime, nodes=nodes.astype(np.int64),
                work=runtime * nodes, jtype=jtype, nodes_total=p["nodes"],
                n_types=p["n_types"], params=p)


def drift_workload(base: dict, loads=None, homogeneous=None,
                   homog_shrinks=None, n_segments: int = 8) -> dict:
    """`n_segments` back-to-back flows on one clock, segment i seeded
    ``base seed + i``, each with its own load / homogeneity / shrink."""
    def each(v, default):
        v = default if v is None else v
        return list(v) if isinstance(v, (list, tuple, np.ndarray)) \
            else [v] * n_segments
    loads = each(loads, base["load"])
    homogeneous = each(homogeneous, base["homogeneous"])
    homog_shrinks = each(homog_shrinks, base["homog_shrink"])
    seg_jobs = base["n_jobs"] // n_segments
    seg_horizon = float(base["horizon"]) / n_segments
    parts = []
    for i in range(n_segments):
        seg = generate(**{**base, "n_jobs": seg_jobs, "horizon": seg_horizon,
                          "load": float(loads[i]),
                          "homogeneous": bool(homogeneous[i]),
                          "homog_shrink": float(homog_shrinks[i]),
                          "seed": base["seed"] + i})
        seg["submit"] = seg["submit"] + i * seg_horizon
        parts.append(seg)
    out = {f: np.concatenate([p[f] for p in parts])
           for f in ("submit", "runtime", "nodes", "work", "jtype")}
    return dict(out, nodes_total=base["nodes"], n_types=base["n_types"],
                params={**base, "n_jobs": seg_jobs * n_segments})


def drift_scenarios(n_jobs: int = 4000, nodes: int = 100, seed: int = 0,
                    n_segments: int = 8) -> dict:
    """The service's five scenarios: a steady control, intensity and
    homogeneity drift as a ramp and as a step."""
    base = {**DEFAULTS, "n_jobs": n_jobs, "nodes": nodes, "load": 0.90,
            "homogeneous": True, "seed": seed, "daily_amplitude": 0.3}
    s = n_segments
    d = lambda **kw: drift_workload(base, n_segments=s, **kw)
    return {
        "steady": d(loads=[0.90] * s),
        "intensity_ramp": d(loads=np.linspace(0.82, 0.96, s)),
        "intensity_step": d(loads=[0.85] * (s // 2) + [0.95] * (s - s // 2)),
        "homogeneity_ramp": d(homog_shrinks=np.linspace(0.15, 0.95, s)),
        "homogeneity_step": d(homogeneous=[True] * (s // 2)
                              + [False] * (s - s // 2)),
    }


def digest(flow: dict) -> dict:
    """sha256 of submit / runtime (rounded to 1e-6 s), nodes and jtype."""
    def h(a, decimals=None):
        a = np.ascontiguousarray(
            np.asarray(a, np.float64).round(decimals) if decimals is not None
            else np.asarray(a, np.int64))
        return hashlib.sha256(a.tobytes()).hexdigest()
    return {"submit": h(flow["submit"], 6), "runtime": h(flow["runtime"], 6),
            "nodes": h(flow["nodes"]), "jtype": h(flow["jtype"])}


def seed_int(*parts: int) -> int:
    """A 63-bit seed made from several whole numbers (any size)."""
    ss = np.random.SeedSequence([int(p) % (1 << 64) for p in parts])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(1))

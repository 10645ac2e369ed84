"""Differential suite: FCFS / EASY-backfill vs brute-force numpy references.

`test_des_equivalence.py` pins the Packet simulator against its seed
implementation; this module gives the two rigid baselines the same
treatment. The references below re-implement the exact event-loop semantics
of `repro.core.schedulers` with plain Python/numpy data structures — list
walks instead of fixed-shape `lax.while_loop` state — so a bug in the JAX
formulation (slot bookkeeping, shadow-time reservation, window clipping)
cannot hide in both implementations at once.

Tie-breaking is part of the contract and is mirrored deliberately:
first-minimal event slot (`argmin`), first-free ring slot (`argmax` over
isinf), submit-before-finish on equal timestamps, and a *stable* sort of
running groups by end time in the backfill reservation pass.

Randomized workloads use quarter-integer times (multiples of 0.25 well
below 2**22), which are exactly representable in float32, so the float32
simulators are compared against the float64 references with zero tolerance
for decision flips. A reduced Lublin workload additionally exercises the
float64 simulation path through the `precision` opt-in with tight
tolerances (identical operation order => agreement to ~ulp).
"""
import numpy as np
import pytest

from repro.core import (pack_workload, precision, resolve_ring,
                        simulate_backfill, simulate_fcfs)
from repro.workload.lublin import WorkloadParams, generate_workload

from conftest import make_workload


def _overlap(a, b, t_end):
    return max(min(b, t_end) - min(a, t_end), 0.0)


class _RefSim:
    """Shared submit/finish event skeleton (mirrors `_event_skeleton`)."""

    def __init__(self, submit, runtime, nodes, s_init, m_nodes, ring):
        self.submit = np.asarray(submit, np.float64)
        self.runtime = np.asarray(runtime, np.float64)
        self.nodes = np.asarray(nodes, np.int64)
        self.s = float(s_init)
        self.N = len(self.submit)
        self.t_end = float(self.submit[-1])
        self.t = 0.0
        self.next_sub = 0
        self.head_ptr = 0
        self.started = np.zeros(self.N, bool)
        self.m_free = int(m_nodes)
        self.grp_end = np.full(ring, np.inf)
        self.grp_m = np.zeros(ring, np.int64)
        self.start_t = np.full(self.N, np.inf)
        self.qlen_int = 0.0
        self.busy = 0.0
        self.useful = 0.0
        self.n_started = 0

    def slot_free(self):
        return bool(np.isinf(self.grp_end).any())

    def start_job(self, i):
        t_fin = self.t + (self.s + self.runtime[i])
        slot = int(np.argmax(np.isinf(self.grp_end)))
        m = int(self.nodes[i])
        self.busy += m * _overlap(self.t, t_fin, self.t_end)
        self.useful += m * _overlap(self.t + self.s, t_fin, self.t_end)
        self.started[i] = True
        self.m_free -= m
        self.grp_end[slot] = t_fin
        self.grp_m[slot] = m
        self.start_t[i] = self.t
        self.n_started += 1

    def run(self, sched_pass, max_iters):
        iters = 0
        while ((self.next_sub < self.N or np.isfinite(self.grp_end).any())
               and iters < max_iters):
            t_sub = (self.submit[self.next_sub]
                     if self.next_sub < self.N else np.inf)
            slot = int(np.argmin(self.grp_end))
            t_fin = self.grp_end[slot]
            take_sub = t_sub <= t_fin
            t_new = t_sub if take_sub else t_fin
            n_wait = self.next_sub - self.n_started
            self.qlen_int += n_wait * _overlap(self.t, t_new, self.t_end)
            self.t = t_new
            if take_sub:
                self.next_sub += 1
            else:
                self.m_free += int(self.grp_m[slot])
                self.grp_end[slot] = np.inf
                self.grp_m[slot] = 0
            sched_pass(self)
            iters += 1
        ok = (self.next_sub >= self.N and not np.isfinite(self.grp_end).any()
              and self.started.all())
        return {
            "start_t": self.start_t, "run_start_t": self.start_t + self.s,
            "qlen_int": self.qlen_int, "busy_ns": self.busy,
            "useful_ns": self.useful, "n_groups": self.n_started,
            "makespan": self.t, "ok": ok,
        }


def ref_fcfs(submit, runtime, nodes, s_init, m_nodes, ring):
    sim = _RefSim(submit, runtime, nodes, s_init, m_nodes, ring)

    def sched(sim):
        while (sim.head_ptr < sim.next_sub
               and sim.nodes[sim.head_ptr] <= sim.m_free and sim.slot_free()):
            sim.start_job(sim.head_ptr)
            sim.head_ptr += 1

    return sim.run(sched, 4 * sim.N + 64)


def ref_backfill(submit, runtime, nodes, s_init, m_nodes, ring,
                 backfill_depth=64, reservations=None):
    """EASY: `reservations` (a dict, if given) gets each head's first
    shadow time, keyed by job index."""
    sim = _RefSim(submit, runtime, nodes, s_init, m_nodes, ring)

    def waiting_idx(sim):
        return [i for i in range(sim.next_sub) if not sim.started[i]]

    def sched(sim):
        # 1) start from the head while it fits
        while True:
            w = waiting_idx(sim)
            if not (w and sim.nodes[w[0]] <= sim.m_free and sim.slot_free()):
                break
            sim.start_job(w[0])

        # 2) reservation for a blocked head: shadow time + extra nodes
        w = waiting_idx(sim)
        any_wait = bool(w)
        head = w[0] if any_wait else 0
        n_head = int(sim.nodes[head]) if any_wait else 1
        order = np.argsort(sim.grp_end, kind="stable")
        frees = np.cumsum(sim.grp_m[order]) + sim.m_free
        enough = frees >= n_head
        shadow = (sim.grp_end[order][int(np.argmax(enough))]
                  if enough.any() else np.inf)
        at_shadow = sim.m_free + int(sim.grp_m[sim.grp_end <= shadow].sum())
        extra = max(at_shadow - n_head, 0)
        if any_wait and reservations is not None:
            reservations.setdefault(head, shadow)

        # 3) up to backfill_depth candidates behind the head, in index
        # order; one that runs past the shadow time uses extra nodes
        for i in [j for j in w if j != head][:backfill_depth]:
            fits_now = sim.nodes[i] <= sim.m_free
            ends_before = sim.t + (sim.s + sim.runtime[i]) <= shadow
            within_extra = sim.nodes[i] <= extra
            if (fits_now and (ends_before or within_extra)
                    and sim.slot_free()):
                sim.start_job(i)
                if not ends_before:
                    extra -= int(sim.nodes[i])

    return sim.run(sched, 4 * sim.N + 64)


def random_quarter_workload(seed):
    """Exact-in-float32 rigid workload: all times are multiples of 0.25."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 60))
    m = int(rng.choice([4, 8, 32]))
    submit = np.cumsum(rng.integers(0, 40, n)) / 4.0
    runtime = rng.integers(1, 400, n) / 4.0
    nodes = rng.integers(1, m + 1, n)
    jtype = rng.integers(0, 4, n)
    s_init = float(rng.choice([0.0, 2.5, 7.25]))
    wl = make_workload(submit, runtime, nodes, jtype, 4, m)
    return wl, s_init, m


def assert_matches_reference(res, ref, rtol=1e-6, atol=1e-6):
    res = {f: np.asarray(getattr(res, f)) for f in ref}
    assert bool(res["ok"]) == ref["ok"]
    for f in ("start_t", "run_start_t", "qlen_int", "busy_ns", "useful_ns",
              "n_groups", "makespan"):
        np.testing.assert_allclose(res[f], ref[f], rtol=rtol, atol=atol,
                                   err_msg=f)


class TestFcfsDifferential:
    @pytest.mark.parametrize("seed", range(8))
    def test_randomized_exact(self, seed):
        wl, s, m = random_quarter_workload(seed)
        pw = pack_workload(wl)
        ring = resolve_ring(m, pw.n_jobs)
        ref = ref_fcfs(wl.submit, wl.runtime, wl.nodes, s, m, ring)
        assert ref["ok"]
        assert_matches_reference(simulate_fcfs(pw, s, m), ref)

    def test_lublin_float64(self, small_workload):
        wl = small_workload
        m = wl.params.nodes
        s = wl.init_time_for_proportion(0.3)
        ring = resolve_ring(m, wl.n_jobs)
        ref = ref_fcfs(wl.submit, wl.runtime, wl.nodes, s, m, ring)
        with precision.dtype_scope(np.float64):
            res = simulate_fcfs(pack_workload(wl, np.float64), s, m)
            assert_matches_reference(res, ref, rtol=1e-9, atol=1e-9)


class TestBackfillDifferential:
    @pytest.mark.parametrize("seed", range(8))
    def test_randomized_exact(self, seed):
        wl, s, m = random_quarter_workload(seed + 100)
        pw = pack_workload(wl)
        ring = resolve_ring(m, pw.n_jobs)
        ref = ref_backfill(wl.submit, wl.runtime, wl.nodes, s, m, ring)
        assert ref["ok"]
        assert_matches_reference(simulate_backfill(pw, s, m), ref)

    def test_lublin_float64(self, small_workload):
        wl = small_workload
        m = wl.params.nodes
        s = wl.init_time_for_proportion(0.2)
        ring = resolve_ring(m, wl.n_jobs)
        ref = ref_backfill(wl.submit, wl.runtime, wl.nodes, s, m, ring)
        with precision.dtype_scope(np.float64):
            res = simulate_backfill(pack_workload(wl, np.float64), s, m)
            assert_matches_reference(res, ref, rtol=1e-9, atol=1e-9)

    @pytest.mark.parametrize("seed", range(6))
    def test_head_never_starts_after_its_reservation(self, seed):
        """EASY's guarantee: with exact runtimes, every job starts by the
        shadow time it was first reserved as the blocked head."""
        wl, s, m = random_quarter_workload(seed + 300)
        ring = resolve_ring(m, wl.n_jobs)
        reserved = {}
        ref = ref_backfill(wl.submit, wl.runtime, wl.nodes, s, m, ring,
                           reservations=reserved)
        assert ref["ok"] and reserved
        for job, shadow in reserved.items():
            assert ref["start_t"][job] <= shadow, (job, shadow)
        assert_matches_reference(simulate_backfill(pack_workload(wl), s, m),
                                 ref)

    def test_hand_case_spends_extra_nodes(self):
        """Two backfill candidates that each fit the head's 2 extra nodes:
        the first takes them, the second waits (tests/test_des.py)."""
        submit, runtime, nodes = ([0, 0, 1, 2, 3], [100, 10, 100, 500, 500],
                                  [6, 4, 8, 2, 2])
        ref = ref_backfill(submit, runtime, nodes, 0.0, 10, 5)
        np.testing.assert_array_equal(ref["start_t"], [0, 0, 100, 10, 200])

    def test_backfill_no_worse_than_fcfs_on_avg_start(self):
        """Sanity cross-check between the two references themselves."""
        for seed in range(4):
            wl, s, m = random_quarter_workload(seed + 200)
            ring = resolve_ring(m, wl.n_jobs)
            f = ref_fcfs(wl.submit, wl.runtime, wl.nodes, s, m, ring)
            b = ref_backfill(wl.submit, wl.runtime, wl.nodes, s, m, ring)
            assert b["start_t"].mean() <= f["start_t"].mean() + 1e-9

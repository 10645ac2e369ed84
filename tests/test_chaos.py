"""Chaos (failure/straggler/requeue) semantics of the DES engines.

Four contracts, in order of strictness:

1. Zero-chaos identity — chaos=None and an inert ChaosConfig (all-zero
   rates) produce bitwise-identical results in BOTH engines and both
   dtypes, and the sweep drivers normalize inert configs to the exact
   pre-chaos compiled programs.
2. Cross-engine parity — with chaos enabled the while and scan engines
   produce identical event sequences: schedules, group logs, and every
   integer/boolean counter agree exactly in both dtypes. Float metric
   accumulates may differ by ulps in either dtype — LLVM's FMA
   contraction is free to round the two engines' differently-shaped
   loop bodies differently — so those are checked allclose (tight in
   float64).
3. Dispatch-layout invariance — run_packet_grid/run_cohort_grid produce
   bitwise-identical Metrics for mode="seq"/"chunked"/"fused": every
   layout runs the same scan engine with grid-order lane ids, so chaos
   draws and rounding cannot depend on how lanes were batched.
4. Differential oracle — hand-computable 2-job failure and straggler
   cascades agree with the host-side ClusterSim (repro.cluster) when its
   rng is scripted to replay the DES lane's uniform stream.
"""
import math
import types
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CHAOS_AXIS_FIELDS, ChaosConfig, chaos_axis_len,
                        chaos_is_inert, chaos_lane_grid,
                        chaos_uniforms, cohort_key, efficiency_metrics,
                        group_workloads, pack_workload, precision,
                        resolve_max_requeues, resolve_ring, run_cohort_grid,
                        run_packet_grid, simulate_packet,
                        simulate_packet_scan, sweep_plan)
from repro.core.sweep import _enforce_budget
from repro.cluster import ClusterConfig, ClusterSim, JobType, MLJob
from repro.workload.lublin import WorkloadParams, generate_workload

from conftest import make_workload


def assert_fields_equal(a, b, fields=None, err=""):
    for f in fields or a._fields:
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), (err, f)


@pytest.fixture(scope="module")
def chaos_workload():
    return generate_workload(WorkloadParams(
        n_jobs=80, nodes=32, load=0.9, homogeneous=True, seed=5))


# cell 0 is straggler-only and cell 1 failure-only: failures take
# precedence over kills at group end, so a harsh MTBF would mask every
# straggler kill in its cell
CHAOS_GRID = ChaosConfig(mtbf_chip_hours=np.asarray([0.0, 0.2]),
                         ckpt_period=120.0,
                         straggler_prob=np.asarray([0.3, 0.0]),
                         straggler_factor=4.0, straggler_deadline=2.0,
                         seed=11)
KS = [0.5, 2.0, 20.0]
SP = [0.05, 0.2]


# ------------------------------------------------------- zero-chaos identity

class TestZeroChaosIdentity:
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    @pytest.mark.parametrize("engine", [simulate_packet,
                                        simulate_packet_scan])
    def test_inert_config_bitwise(self, chaos_workload, dtype, engine):
        m = chaos_workload.params.nodes
        with precision.dtype_scope(dtype):
            pw = pack_workload(chaos_workload, dtype)
            ring = resolve_ring(m, pw.n_jobs)
            k = jnp.asarray(2.0, dtype)
            s = jnp.asarray(chaos_workload.init_time_for_proportion(0.2),
                            dtype)
            r0 = engine(pw, k, s, m, ring=ring)
            rz = engine(pw, k, s, m, ring=ring, chaos=ChaosConfig())
        assert_fields_equal(r0, rz, err=engine.__name__)

    def test_inert_detection(self):
        assert chaos_is_inert(None)
        assert chaos_is_inert(ChaosConfig())
        assert chaos_is_inert(ChaosConfig(ckpt_period=60.0, seed=9))
        assert not chaos_is_inert(ChaosConfig(mtbf_chip_hours=1.0))
        assert not chaos_is_inert(ChaosConfig(straggler_prob=0.5))
        assert not chaos_is_inert(
            ChaosConfig(mtbf_chip_hours=np.asarray([0.0, 0.1])))

    @pytest.mark.parametrize("mode", ["seq", "chunked"])
    def test_grid_normalizes_inert_config(self, chaos_workload, mode):
        kw = dict(mode=mode)
        if mode == "chunked":
            kw["chunk_lanes"] = 4
        g0 = run_packet_grid(chaos_workload, KS, SP, **kw)
        gz = run_packet_grid(chaos_workload, KS, SP, chaos=ChaosConfig(),
                             **kw)
        assert_fields_equal(g0, gz, err=mode)
        assert g0.avg_wait.shape == (len(KS), len(SP))

    def test_cohort_key_normalizes_inert_config(self, chaos_workload):
        assert cohort_key(chaos_workload, chaos=ChaosConfig()) == \
            cohort_key(chaos_workload)
        assert cohort_key(chaos_workload, chaos=ChaosConfig()).max_requeues \
            == 0


# ---------------------------------------------------- cross-engine parity

class TestEngineChaosParity:
    # schedules and integer/boolean outputs must agree exactly in every
    # dtype; float metric accumulates only up to FMA-contraction ulps
    # (see the module docstring)
    EXACT = ("start_t", "run_start_t", "n_groups", "makespan", "ok",
             "budget_exhausted", "failures", "straggler_kills", "requeues",
             "requeued_jobs")

    @pytest.mark.parametrize("lane", [0, 2, 7])
    @pytest.mark.parametrize("dtype,rtol", [(np.float32, 1e-5),
                                            (np.float64, 1e-12)])
    def test_engines_agree(self, chaos_workload, lane, dtype, rtol):
        m = chaos_workload.params.nodes
        ch = ChaosConfig(mtbf_chip_hours=0.02, ckpt_period=120.0,
                         straggler_prob=0.3, seed=11, lane=lane)
        with precision.dtype_scope(dtype):
            pw = pack_workload(chaos_workload, dtype)
            ring = resolve_ring(m, pw.n_jobs)
            k = jnp.asarray(0.5, dtype)
            s = jnp.asarray(chaos_workload.init_time_for_proportion(0.2),
                            dtype)
            rw = simulate_packet(pw, k, s, m, ring=ring, chaos=ch)
            rs = simulate_packet_scan(pw, k, s, m, ring=ring, chaos=ch)
        assert bool(rw.ok) and int(rw.failures) > 0
        assert_fields_equal(rw, rs, fields=self.EXACT, err=f"lane {lane}")
        for f in set(rw._fields) - set(self.EXACT):
            np.testing.assert_allclose(np.asarray(getattr(rw, f)),
                                       np.asarray(getattr(rs, f)),
                                       rtol=rtol, err_msg=f)


# ----------------------------------------------- dispatch-layout invariance

class TestSweepChaosParity:
    def test_seq_chunked_fused_bitwise(self, chaos_workload):
        g_seq = run_packet_grid(chaos_workload, KS, SP, mode="seq",
                                chaos=CHAOS_GRID)
        g_chk = run_packet_grid(chaos_workload, KS, SP, mode="chunked",
                                chunk_lanes=4, chaos=CHAOS_GRID)
        g_fus = run_packet_grid(chaos_workload, KS, SP, mode="fused",
                                chaos=CHAOS_GRID)
        C = chaos_axis_len(CHAOS_GRID)
        assert C == 2
        assert g_seq.avg_wait.shape == (len(KS), len(SP), C)
        assert_fields_equal(g_seq, g_chk, err="seq vs chunked")
        assert_fields_equal(g_seq, g_fus, err="seq vs fused")
        # each chaos cell fires exactly its own fault kind
        assert int(np.sum(g_seq.straggler_kills[..., 0])) > 0
        assert int(np.sum(g_seq.failures[..., 0])) == 0
        assert int(np.sum(g_seq.failures[..., 1])) > 0
        assert int(np.sum(g_seq.straggler_kills[..., 1])) == 0

    def test_chaos_axis_len_validates(self):
        with pytest.raises(ValueError):
            chaos_axis_len(ChaosConfig(
                mtbf_chip_hours=np.asarray([0.1, 0.2]),
                straggler_prob=np.asarray([0.1, 0.2, 0.3])))

    def test_cohort_matches_per_workload(self, chaos_workload):
        wl2 = generate_workload(WorkloadParams(
            n_jobs=80, nodes=32, load=0.9, homogeneous=True, seed=9))
        cohorts = group_workloads({"a": chaos_workload, "b": wl2},
                                  np.float32, chaos=CHAOS_GRID)
        assert len(cohorts) == 1
        assert cohorts[0].key.max_requeues == \
            resolve_max_requeues(CHAOS_GRID, 80)
        gc = run_cohort_grid(cohorts[0], KS, SP, mode="fused",
                             chaos=CHAOS_GRID)
        gc_chk = run_cohort_grid(cohorts[0], KS, SP, mode="chunked",
                                 chunk_lanes=4, chaos=CHAOS_GRID)
        ga = run_packet_grid(chaos_workload, KS, SP, mode="fused",
                             chaos=CHAOS_GRID)
        assert_fields_equal(gc["a"], ga, err="cohort vs per-workload")
        for name in ("a", "b"):
            assert_fields_equal(gc[name], gc_chk[name], err=name)

    def test_sweep_plan_records_chaos(self):
        plan = sweep_plan("auto", len(KS) * len(SP), chaos=CHAOS_GRID)
        assert plan["n_lanes"] == len(KS) * len(SP) * 2
        ch = plan["chaos"]
        assert ch["axis_len"] == 2 and ch["seed"] == 11
        assert ch["mtbf_chip_hours"] == pytest.approx([0.0, 0.2])
        assert ch["straggler_prob"] == pytest.approx([0.3, 0.0])
        # inert configs vanish from the plan like they do from the run
        assert "chaos" not in sweep_plan("auto", 6, chaos=ChaosConfig())


# ---------------------------------------------- chaos-axis error reporting

class TestChaosAxisValidation:
    def test_mismatched_lengths_name_fields(self):
        bad = ChaosConfig(mtbf_chip_hours=np.asarray([0.1, 0.2]),
                          straggler_prob=np.asarray([0.1, 0.2, 0.3]))
        with pytest.raises(ValueError) as ei:
            chaos_axis_len(bad)
        msg = str(ei.value)
        assert "mtbf_chip_hours[2]" in msg
        assert "straggler_prob[3]" in msg

    def test_2d_param_names_field(self):
        with pytest.raises(ValueError,
                           match=r"ckpt_period must be a scalar or a 1-D"):
            chaos_axis_len(ChaosConfig(mtbf_chip_hours=0.1,
                                       ckpt_period=np.ones((2, 2))))

    def test_scalar_array_mix_broadcasts(self):
        mix = ChaosConfig(mtbf_chip_hours=np.asarray([0.1, 0.2]),
                          ckpt_period=120.0,
                          straggler_prob=np.asarray([0.3]))
        assert chaos_axis_len(mix) == 2      # len-1 arrays broadcast too
        lanes, C = chaos_lane_grid(mix, 3, np.float32)
        assert C == 2
        for name in CHAOS_AXIS_FIELDS:
            assert np.shape(getattr(lanes, name)) == (6,), name
        np.testing.assert_allclose(np.asarray(lanes.mtbf_chip_hours),
                                   [0.1, 0.2] * 3, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(lanes.straggler_prob),
                                   [0.3] * 6, rtol=1e-6)
        assert np.array_equal(np.asarray(lanes.lane), np.arange(6))

    def test_lane_grid_propagates_named_error(self):
        bad = ChaosConfig(mtbf_chip_hours=np.asarray([0.1, 0.2]),
                          straggler_deadline=np.asarray([2.0, 2.0, 2.0]))
        with pytest.raises(ValueError, match=r"straggler_deadline\[3\]"):
            chaos_lane_grid(bad, 4, np.float32)


# -------------------------------------------------- ClusterSim differential

class ScriptedRng:
    """Replays a DES lane's uniform stream into ClusterSim's rng calls.

    ClusterSim draws `random()` once per group at formation (straggler)
    and `exponential(scale)` once per group at its finish (failure). With
    single-type sequential groups both orders equal formation order, so
    row g of `chaos_uniforms` maps onto ClusterSim's g-th draw of each
    kind; an exponential draw is the inverse-CDF of the failure uniform,
    exactly the scan engine's `t_fail` formula.
    """

    def __init__(self, u):
        self.u = np.asarray(u)
        self.n_random = 0
        self.n_exp = 0
        self.exp_scales = []

    def random(self):
        v = float(self.u[self.n_random, 0])
        self.n_random += 1
        return v

    def exponential(self, scale):
        self.exp_scales.append(float(scale))
        v = -math.log(max(float(self.u[self.n_exp, 1]), 5e-324)) * scale
        self.n_exp += 1
        return v


def _hand_des(chaos, submit, runtime, k, s=100.0, m=4):
    """Run both engines on a single-type hand workload (nodes=1 jobs, so
    work == runtime) and return (while_result, scan_result, uniforms)."""
    n = len(submit)
    with precision.dtype_scope(np.float64):
        wl = make_workload(submit, runtime, [1] * n, [0] * n, 1, m)
        pw = pack_workload(wl, np.float64)
        ring = resolve_ring(m, n)
        cap = n + resolve_max_requeues(chaos, n)
        u = np.asarray(chaos_uniforms(chaos, np.float64, cap))
        rw = simulate_packet(pw, jnp.float64(k), jnp.float64(s), m,
                             ring=ring, chaos=chaos)
        rs = simulate_packet_scan(pw, jnp.float64(k), jnp.float64(s), m,
                                  ring=ring, chaos=chaos)
    return rw, rs, u


def _hand_cluster(cfg, submit, runtime, u, s=100.0):
    sim = ClusterSim([JobType("t0", init_time=s, tp_degree=1)], cfg)
    sim.rng = ScriptedRng(u)
    for i, (t, w) in enumerate(zip(submit, runtime)):
        sim.submit(MLJob(jid=i, jtype=0, submit=float(t), work=float(w)))
    return sim, sim.run()


def _two_job_des(chaos, k, s=100.0, m=4):
    with precision.dtype_scope(np.float64):
        wl = make_workload([0.0, 0.0], [6000.0, 6000.0], [1, 1], [0, 0],
                           1, m)
        pw = pack_workload(wl, np.float64)
        ring = resolve_ring(m, 2)
        cap = 2 + resolve_max_requeues(chaos, 2)
        u = np.asarray(chaos_uniforms(chaos, np.float64, cap))
        rw = simulate_packet(pw, jnp.float64(k), jnp.float64(s), m,
                             ring=ring, chaos=chaos)
        rs = simulate_packet_scan(pw, jnp.float64(k), jnp.float64(s), m,
                                  ring=ring, chaos=chaos)
    return rw, rs, u


def _two_job_cluster(cfg, u, s=100.0):
    sim = ClusterSim([JobType("t0", init_time=s, tp_degree=1)], cfg)
    sim.rng = ScriptedRng(u)
    sim.submit(MLJob(jid=0, jtype=0, submit=0.0, work=6000.0))
    sim.submit(MLJob(jid=1, jtype=0, submit=0.0, work=6000.0))
    return sim, sim.run()


class TestClusterSimDifferential:
    def test_failure_requeue_case(self):
        """Group 1 (job A alone, dur 1600) fails mid-run; its checkpointed
        remainder pools with job B into group 2, which survives.

        Hand model (s=100, M=4, k=2, mtbf=1 chip-hour, ckpt=300; seed 82
        picked so t_fail1 in (500, 1500) and group 2 survives):
          t_fail   = t0 - ln(u2) * mtbf*3600/m = -ln(u[0,1]) * 900
          run_done = t_fail - 100;  ckpt_done = 300*floor(run_done/300)
          lost     = (run_done - ckpt_done) * 4
          group 2  = B + A-remainder = 12000 - 4*ckpt_done chip-s at
                     t=1600, dur2 = 100 + work2/4.
        """
        chaos = ChaosConfig(mtbf_chip_hours=1.0, ckpt_period=300.0,
                            seed=82, lane=0)
        rw, rs, u = _two_job_des(chaos, k=2.0)
        t_fail = -math.log(max(u[0, 1], 5e-324)) * 900.0
        assert 500.0 < t_fail < 1500.0
        ckpt_done = 300.0 * math.floor((t_fail - 100.0) / 300.0)
        lost = (t_fail - 100.0 - ckpt_done) * 4
        dur2 = 100.0 + (12000.0 - 4 * ckpt_done) / 4.0

        for eng, r in (("while", rw), ("scan", rs)):
            assert bool(r.ok), eng
            assert int(r.n_groups) == 2, eng
            assert int(r.failures) == 1 and int(r.requeues) == 1, eng
            assert int(r.straggler_kills) == 0, eng
            assert float(r.lost_work) == pytest.approx(lost, rel=1e-12), eng
            assert float(r.makespan) == pytest.approx(1600.0 + dur2), eng
            np.testing.assert_allclose(np.asarray(r.start_t),
                                       [0.0, 1600.0], err_msg=eng)

        cfg = ClusterConfig(n_chips=4, scale_ratio=2.0, ckpt_period=300.0,
                            mtbf_chip_hours=1.0)
        sim, cm = _two_job_cluster(cfg, u)
        assert cm["groups"] == 2 and cm["failures"] == 1
        assert cm["requeues"] == 1 and cm["straggler_kills"] == 0
        assert cm["unfinished"] == 0
        assert cm["lost_chip_seconds"] == pytest.approx(lost, rel=1e-12)
        assert cm["makespan"] == pytest.approx(1600.0 + dur2)
        # the scripted draw really used the slice failure rate m/MTBF
        assert sim.rng.exp_scales == [900.0, 900.0]
        # requeued-then-completed job A reports its LAST completion time
        assert sim.jobs[0].finish == pytest.approx(1600.0 + dur2)
        assert sim.jobs[1].finish == pytest.approx(1600.0 + dur2)

    def test_straggler_cascade_case(self):
        """Every group stretches 4x against a 2x deadline: a kill cascade
        whose arithmetic is dyadic, hence exact in float64.

        Hand model (s=100, M=4, k=0.25, prob=1, factor=4, deadline=2):
        each round runs to its deadline 2*(100 + work/4), credits
        (deadline - 100) chip-seconds/chip / factor, and requeues the
        rest; work shrinks 12000 -> 8900 -> ... until round 7 fits its
        deadline. Ends at exactly t=12700 after 6 kills. `max_requeues=8`
        keeps the DES injection gate open for all rounds (ClusterSim is
        uncapped).
        """
        chaos = ChaosConfig(straggler_prob=1.0, straggler_factor=4.0,
                            straggler_deadline=2.0, seed=0, lane=0,
                            max_requeues=8)
        rw, rs, u = _two_job_des(chaos, k=0.25)
        for eng, r in (("while", rw), ("scan", rs)):
            assert bool(r.ok), eng
            assert int(r.n_groups) == 7, eng
            assert int(r.straggler_kills) == 6, eng
            assert int(r.requeues) == 6 and int(r.failures) == 0, eng
            assert float(r.lost_work) == 0.0, eng
            assert float(r.makespan) == 12700.0, eng

        cfg = ClusterConfig(n_chips=4, scale_ratio=0.25, straggler_prob=1.0,
                            straggler_factor=4.0, straggler_deadline=2.0)
        sim, cm = _two_job_cluster(cfg, u)
        assert cm["groups"] == 7 and cm["straggler_kills"] == 6
        assert cm["requeues"] == 6 and cm["failures"] == 0
        assert cm["unfinished"] == 0
        assert cm["makespan"] == 12700.0

    def test_partial_credit_splits_inside_member(self):
        """Group 2 = {B(4000), C(6000)} fails with checkpoint credit 6000
        chip-s: B completes inside the credit, C requeues alone with a
        2000 chip-s residual. The remnant must be ONE member (oldest
        submit 2.0) — the pre-fix aggregate pool re-queued the whole
        member count (2) because it never knew where the credit landed.

        Hand model (s=100, M=4, k=0.25, mtbf=1 chip-hour, ckpt=300;
        seed 118 picked so groups 1 and 3 survive while group 2 fails at
        t_fail in [1600, 1900) => ckpt_done 1500, credit 4*1500=6000):
          A: submit 0, work 6000 -> group 1 [0, 1600), all 4 chips
          B, C: submit 1, 2 -> queue; group 2 at t=1600, work 10000,
             dur 2600, fails; credit 6000 = B's 4000 + 2000 into C
          group 3 at t=4200: {C}, work 4000, dur 1100 -> makespan 5300.
        """
        chaos = ChaosConfig(mtbf_chip_hours=1.0, ckpt_period=300.0,
                            seed=118, lane=0)
        submit = [0.0, 1.0, 2.0]
        runtime = [6000.0, 4000.0, 6000.0]
        rw, rs, u = _hand_des(chaos, submit, runtime, k=0.25)
        t_fails = [-math.log(max(u[g, 1], 5e-324)) * 900.0 for g in range(3)]
        assert t_fails[0] > 1600.0 and t_fails[2] > 1100.0
        assert 1600.0 <= t_fails[1] < 1900.0     # => ckpt_done == 1500
        lost = (t_fails[1] - 100.0 - 1500.0) * 4

        for eng, r in (("while", rw), ("scan", rs)):
            assert bool(r.ok), eng
            assert int(r.n_groups) == 3, eng
            assert int(r.failures) == 1 and int(r.requeues) == 1, eng
            # the fix under test: one member requeued, not the pool's 2
            assert int(r.requeued_jobs) == 1, eng
            assert float(r.lost_work) == pytest.approx(lost, rel=1e-12), eng
            assert float(r.makespan) == 5300.0, eng
            np.testing.assert_allclose(np.asarray(r.start_t),
                                       [0.0, 1600.0, 1600.0], err_msg=eng)

        cfg = ClusterConfig(n_chips=4, scale_ratio=0.25, ckpt_period=300.0,
                            mtbf_chip_hours=1.0)
        sim, cm = _hand_cluster(cfg, submit, runtime, u)
        assert cm["groups"] == 3 and cm["failures"] == 1
        assert cm["requeues"] == 1 and cm["requeued_jobs"] == 1
        assert cm["unfinished"] == 0
        assert cm["lost_chip_seconds"] == pytest.approx(lost, rel=1e-12)
        assert cm["makespan"] == 5300.0
        # B finished by the requeue credit at group 2's end; C ran again
        assert sim.jobs[1].finish == 4200.0
        assert sim.jobs[2].finish == 5300.0

    def test_residual_carry_across_requeues(self):
        """One job killed four times: each walk must start from the
        pool's carried residual, or the remnant work (and every later
        duration) is wrong — dropping res0 gives a remnant of 4450
        instead of 1350 in round 2 alone.

        Hand model (s=100, M=4, k=0.25, prob=1, factor=4, deadline=2),
        all dyadic: deadline-kill credits 3100, 1550, 775, 387.5
        accumulate on the single member; remainders 2900 -> 1350 -> 575
        -> 187.5; round 5 fits its deadline (287.5 <= 293.75). Ends at
        3200 + 1650 + 875 + 487.5 + 287.5 = 6500 exactly.
        """
        chaos = ChaosConfig(straggler_prob=1.0, straggler_factor=4.0,
                            straggler_deadline=2.0, seed=0, lane=0,
                            max_requeues=8)
        submit, runtime = [0.0], [6000.0]
        rw, rs, u = _hand_des(chaos, submit, runtime, k=0.25)
        for eng, r in (("while", rw), ("scan", rs)):
            assert bool(r.ok), eng
            assert int(r.n_groups) == 5, eng
            assert int(r.straggler_kills) == 4 and int(r.requeues) == 4, eng
            assert int(r.requeued_jobs) == 4, eng
            assert float(r.lost_work) == 0.0, eng
            assert float(r.makespan) == 6500.0, eng

        cfg = ClusterConfig(n_chips=4, scale_ratio=0.25, straggler_prob=1.0,
                            straggler_factor=4.0, straggler_deadline=2.0)
        sim, cm = _hand_cluster(cfg, submit, runtime, u)
        assert cm["groups"] == 5 and cm["straggler_kills"] == 4
        assert cm["requeues"] == 4 and cm["requeued_jobs"] == 4
        assert cm["unfinished"] == 0 and cm["makespan"] == 6500.0


# ----------------------------------------------------- budget exhaustion

class TestBudgetExhaustion:
    def _truncated_metrics(self, wl):
        m = wl.params.nodes
        pw = pack_workload(wl, np.float32)
        ring = resolve_ring(m, pw.n_jobs)
        k = jnp.float32(2.0)
        s = jnp.float32(wl.init_time_for_proportion(0.2))
        res = simulate_packet_scan(pw, k, s, m, ring=ring, budget=8, seg=8)
        return efficiency_metrics(pw.submit, res, m, pw.t_last_submit)

    def test_tiny_budget_flags_scan(self, chaos_workload):
        met = self._truncated_metrics(chaos_workload)
        assert bool(met.budget_exhausted) and not bool(met.ok)

    def test_tiny_iteration_cap_flags_while(self, chaos_workload):
        m = chaos_workload.params.nodes
        pw = pack_workload(chaos_workload, np.float32)
        ring = resolve_ring(m, pw.n_jobs)
        s = jnp.float32(chaos_workload.init_time_for_proportion(0.2))
        res = simulate_packet(pw, jnp.float32(2.0), s, m, ring=ring,
                              max_iters=3)
        assert bool(res.budget_exhausted) and not bool(res.ok)

    def test_enforce_budget_policies(self, chaos_workload):
        met = self._truncated_metrics(chaos_workload)
        with pytest.raises(RuntimeError, match="event budget"):
            _enforce_budget(met, "raise", "test")
        with pytest.warns(RuntimeWarning, match="event budget"):
            _enforce_budget(met, "warn", "test")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            _enforce_budget(met, "ignore", "test")
        with pytest.raises(ValueError):
            _enforce_budget(met, "explode", "test")

    def test_enforce_budget_names_grid_cells(self):
        bad = np.zeros((3, 2), bool)
        bad[0, 1] = bad[2, 0] = True
        met = types.SimpleNamespace(budget_exhausted=bad)
        with pytest.raises(RuntimeError) as ei:
            _enforce_budget(met, "raise", "grid", ks=KS, s_props=SP)
        msg = str(ei.value)
        assert "2 lane(s)" in msg
        assert "(i_k=0, i_s=1, k=0.5, s_prop=0.2)" in msg
        assert "(i_k=2, i_s=0, k=20, s_prop=0.05)" in msg

    def test_enforce_budget_names_chaos_cells(self):
        bad = np.zeros((2, 2, 3), bool)
        bad[1, 0, 2] = True
        met = types.SimpleNamespace(budget_exhausted=bad)
        with pytest.raises(RuntimeError, match=r"i_k=1, i_s=0, i_chaos=2"):
            _enforce_budget(met, "raise", "grid")

    def test_enforce_budget_truncates_flat_lanes(self):
        met = types.SimpleNamespace(budget_exhausted=np.ones(12, bool))
        with pytest.raises(RuntimeError) as ei:
            _enforce_budget(met, "raise", "flat")
        msg = str(ei.value)
        assert "lane=0" in msg and "lane=7" in msg
        assert "lane=8" not in msg and "... 4 more" in msg

    def test_enforce_budget_scalar_experiment(self, chaos_workload):
        met = self._truncated_metrics(chaos_workload)
        with pytest.raises(RuntimeError, match="the single experiment"):
            _enforce_budget(met, "raise", "one-shot")

    def test_grid_budget_clean_under_chaos(self, chaos_workload):
        """The sized budget (3N + 2R + slack) drains every chaos lane: the
        default on_budget_exhausted='raise' passes untripped."""
        g = run_packet_grid(chaos_workload, KS, SP, mode="fused",
                            chaos=CHAOS_GRID, on_budget_exhausted="raise")
        assert not np.asarray(g.budget_exhausted).any()
        assert np.asarray(g.ok).all()

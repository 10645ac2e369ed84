"""Behaviour tests for the Packet DES and baseline schedulers.

Property-based tests live in ``test_des_properties.py`` behind an optional
``hypothesis`` dev dependency; this module must import cleanly in a minimal
environment so tier-1 collection never fails.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (efficiency_metrics, pack_workload, simulate_backfill,
                        simulate_fcfs, simulate_packet)
from repro.workload.lublin import Workload, WorkloadParams, generate_workload

from conftest import make_workload as _mk_workload


class TestPacketHandConstructed:
    def test_single_job_starts_immediately(self):
        wl = _mk_workload([0.0], [100.0], [1], [0], 2, 10)
        pw = pack_workload(wl)
        res = simulate_packet(pw, 1.0, 50.0, 10)
        assert bool(res.ok)
        assert float(res.start_t[0]) == 0.0
        # work=100, k=1, s=50 -> m=2, exec 50s, done at 100
        assert float(res.makespan) == pytest.approx(100.0)

    def test_group_amortizes_init(self):
        # two same-type jobs queued while node busy -> one group, one init
        wl = _mk_workload([0.0, 1.0, 2.0], [100.0, 40.0, 60.0],
                          [1, 1, 1], [0, 0, 0], 1, 1)
        pw = pack_workload(wl)
        res = simulate_packet(pw, 1000.0, 10.0, 1)  # huge k -> 1 node
        assert bool(res.ok)
        # job0 group: init 10 + 100 exec -> ends 110
        # jobs 1,2 form ONE group at t=110: init 10, then 40 + 60
        assert float(res.start_t[1]) == pytest.approx(110.0)
        assert float(res.start_t[2]) == pytest.approx(110.0)
        assert float(res.run_start_t[1]) == pytest.approx(120.0)
        assert float(res.run_start_t[2]) == pytest.approx(160.0)
        assert float(res.makespan) == pytest.approx(220.0)
        assert int(res.n_groups) == 2

    def test_scale_ratio_sets_group_width(self):
        # paper Fig 3: s=60, work=240 -> k=0.5 gives 8 nodes
        wl = _mk_workload([0.0, 0.0], [120.0, 120.0], [1, 1], [0, 0], 1, 100)
        pw = pack_workload(wl)
        res = simulate_packet(pw, 0.5, 60.0, 100)
        assert bool(res.ok)
        # 8 nodes -> exec 30s, makespan 90
        assert float(res.makespan) == pytest.approx(90.0)

    def test_types_get_separate_groups(self):
        wl = _mk_workload([0.0, 0.0], [100.0, 100.0], [1, 1], [0, 1], 2, 100)
        pw = pack_workload(wl)
        res = simulate_packet(pw, 1.0, 100.0, 100)
        assert bool(res.ok)
        assert int(res.n_groups) == 2  # different types never merge
        # both can start at t=0 (enough nodes)
        np.testing.assert_allclose(np.asarray(res.start_t), 0.0, atol=1e-5)

    def test_not_enough_free_nodes_uses_all_free(self):
        # paper step 4: m_group = min(m_threshold, m_free)
        wl = _mk_workload([0.0], [100.0], [1], [0], 1, 2)
        pw = pack_workload(wl)
        res = simulate_packet(pw, 0.1, 10.0, 2)  # threshold 100 >> 2 free
        assert bool(res.ok)
        # runs on 2 nodes: init 10 + 100/2 -> makespan 60
        assert float(res.makespan) == pytest.approx(60.0)


class TestFcfsBackfill:
    def test_fcfs_blocks_behind_head(self):
        # head needs 4 nodes (busy), small job behind must wait under FCFS
        wl = _mk_workload([0.0, 1.0, 2.0], [100.0, 100.0, 10.0],
                          [4, 4, 1], [0, 0, 0], 1, 4)
        pw = pack_workload(wl)
        res = simulate_fcfs(pw, 0.0, 4)
        assert bool(res.ok)
        assert float(res.start_t[2]) >= float(res.start_t[1])

    def test_backfill_lets_small_job_jump(self):
        # M=5: job0 holds 4 nodes till t=100; head job1 needs 4 (blocked,
        # 1 free); job2 (1 node, 10s) ends before the shadow time (100)
        # -> backfills immediately at its submit t=2.
        wl = _mk_workload([0.0, 1.0, 2.0], [100.0, 100.0, 10.0],
                          [4, 4, 1], [0, 0, 0], 1, 5)
        pw = pack_workload(wl)
        res = simulate_backfill(pw, 0.0, 5)
        assert bool(res.ok)
        assert float(res.start_t[2]) == pytest.approx(2.0)

    def test_backfill_never_delays_head_reservation(self):
        # job2 runs past the shadow but fits in the `extra` node, so the
        # reserved head job must still start exactly at its FCFS time
        wl = _mk_workload([0.0, 1.0, 2.0], [100.0, 100.0, 200.0],
                          [4, 4, 1], [0, 0, 0], 1, 5)
        pw = pack_workload(wl)
        f = simulate_fcfs(pw, 0.0, 5)
        b = simulate_backfill(pw, 0.0, 5)
        assert float(b.start_t[2]) == pytest.approx(2.0)  # used extra node
        assert float(b.start_t[1]) <= float(f.start_t[1]) + 1e-5

    def test_backfill_spends_extra_nodes(self):
        # M=10: A (6 nodes) and X (4) at t=0; head H (8) at t=1; B and C
        # (2 nodes, 500 s each) at t=2, 3. X ends at 10: H is reserved
        # t=100 with 2 extra nodes. B takes them; C would delay H, so it
        # waits for H to end. FCFS starts B only with H.
        wl = _mk_workload([0.0, 0.0, 1.0, 2.0, 3.0],
                          [100.0, 10.0, 100.0, 500.0, 500.0],
                          [6, 4, 8, 2, 2], [0] * 5, 1, 10)
        pw = pack_workload(wl)
        b = simulate_backfill(pw, 0.0, 10)
        f = simulate_fcfs(pw, 0.0, 10)
        assert bool(b.ok) and bool(f.ok)
        np.testing.assert_array_equal(np.asarray(b.start_t),
                                      [0, 0, 100, 10, 200])
        np.testing.assert_array_equal(np.asarray(f.start_t),
                                      [0, 0, 100, 100, 200])
        # the head starts at its reservation, not later
        assert float(b.start_t[2]) <= 100.0


class TestMetrics:
    def test_metrics_hand_computed(self):
        wl = _mk_workload([0.0, 10.0], [100.0, 100.0], [1, 1], [0, 0], 1, 2)
        pw = pack_workload(wl)
        # k huge -> each group 1 node; job0 at t0 (init 5 + 100);
        # job1 arrives t=10, one node still free -> starts immediately too.
        res = simulate_packet(pw, 1e6, 5.0, 2)
        m = jax.tree.map(float, efficiency_metrics(
            pw.submit, res, 2, pw.t_last_submit))
        assert m["avg_wait"] if isinstance(m, dict) else True
        assert m.avg_wait == pytest.approx(0.0, abs=1e-4)
        assert m.med_wait == pytest.approx(0.0, abs=1e-4)
        # window = 10s; job0 busy whole window on 1 of 2 nodes; job1 starts
        # at 10 (zero-length contribution). busy = 10, useful = 5 (init 5).
        assert m.full_util == pytest.approx(10.0 / 20.0)
        assert m.useful_util == pytest.approx(5.0 / 20.0)

    def test_queue_length_integral(self):
        # one node; job0 starts alone at t=0 (init 1 + 10 -> ends 11);
        # jobs 1,2 (submitted just after) wait 11s each, then run as ONE
        # group (init 1 + 20 -> ends 32); job3 at t=50 starts immediately.
        wl = _mk_workload([0.0, 0.0, 0.0, 50.0], [10.0, 10.0, 10.0, 10.0],
                          [1, 1, 1, 1], [0, 0, 0, 0], 1, 1)
        pw = pack_workload(wl)
        res = simulate_packet(pw, 1e6, 1.0, 1)
        assert int(res.n_groups) == 3
        m = jax.tree.map(float, efficiency_metrics(
            pw.submit, res, 1, pw.t_last_submit))
        # qlen integral = 2 jobs x 11 s; window = 50 s
        assert m.avg_qlen == pytest.approx(2 * 11.0 / 50.0, rel=1e-5)
        np.testing.assert_allclose(np.asarray(res.start_t),
                                   [0.0, 11.0, 11.0, 50.0], atol=1e-4)


class TestGeneratedWorkloadsEndToEnd:
    def test_full_small_workload(self, small_workload):
        pw = pack_workload(small_workload)
        s = small_workload.init_time_for_proportion(0.3)
        res = jax.tree.map(np.asarray, simulate_packet(
            pw, 2.0, s, small_workload.params.nodes))
        assert res.ok
        m = efficiency_metrics(pw.submit, jax.tree.map(jnp.asarray, res),
                               small_workload.params.nodes, pw.t_last_submit)
        m = jax.tree.map(float, m)
        assert 0.0 < m.full_util <= 1.0
        assert 0.0 < m.useful_util <= m.full_util + 1e-6

    def test_paper_trend_wait_decreases_with_k(self, small_workload):
        """Headline paper claim: queue time falls as k rises, then plateaus."""
        pw = pack_workload(small_workload)
        M = small_workload.params.nodes
        s = small_workload.init_time_for_proportion(0.3)
        f = jax.jit(lambda k: efficiency_metrics(
            pw.submit, simulate_packet(pw, k, s, M), M, pw.t_last_submit))
        waits = [float(f(k).avg_wait) for k in (0.5, 2.0, 8.0, 50.0, 500.0, 1000.0)]
        assert waits[0] > waits[-1]          # overall decrease
        assert waits[2] > waits[-1] * 0.5 or waits[2] >= waits[-1]  # monotone-ish
        # plateau: k=500 vs k=1000 nearly identical
        assert waits[-2] == pytest.approx(waits[-1], rel=0.1, abs=5.0)

    def test_paper_trend_full_util_decreases_with_k(self, small_workload):
        pw = pack_workload(small_workload)
        M = small_workload.params.nodes
        s = small_workload.init_time_for_proportion(0.3)
        f = jax.jit(lambda k: efficiency_metrics(
            pw.submit, simulate_packet(pw, k, s, M), M, pw.t_last_submit))
        full_low_k = float(f(0.3).full_util)
        full_high_k = float(f(200.0).full_util)
        assert full_low_k > full_high_k

    def test_grouping_beats_backfill_at_high_init(self, small_workload):
        """Predecessor-paper claim: at high init proportion, grouping
        outperforms per-job backfill on queue time."""
        pw = pack_workload(small_workload)
        M = small_workload.params.nodes
        s = small_workload.init_time_for_proportion(0.5)
        g = jax.tree.map(np.asarray, simulate_packet(pw, 10.0, s, M))
        b = jax.tree.map(np.asarray, simulate_backfill(pw, s, M))
        mg = efficiency_metrics(pw.submit, jax.tree.map(jnp.asarray, g), M, pw.t_last_submit)
        mb = efficiency_metrics(pw.submit, jax.tree.map(jnp.asarray, b), M, pw.t_last_submit)
        assert float(mg.avg_wait) < float(mb.avg_wait)


def test_vmap_k_sweep_matches_sequential(small_workload):
    """Batched scale-ratio sweep (one XLA program) == per-k execution."""
    import numpy as np
    from repro.core import run_packet_grid
    ks = [0.5, 2.0, 8.0, 50.0]
    a = run_packet_grid(small_workload, ks=ks, s_props=[0.05, 0.3])
    b = run_packet_grid(small_workload, ks=ks, s_props=[0.05, 0.3],
                        vmap_k=True)
    for f in ("avg_wait", "med_wait", "avg_qlen", "full_util",
              "useful_util"):
        np.testing.assert_allclose(getattr(a, f), getattr(b, f),
                                   rtol=1e-5, err_msg=f)
    assert np.asarray(b.ok).all()

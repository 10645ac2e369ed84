"""Sweep-layout unit tests: mode resolution/validation, lane scheduling
helpers, padded device sharding, and the hardened `plateau_threshold`.

The padded-sharding test runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the in-process
backend is pinned to one CPU device by conftest), proving the paper's
222-style non-divisible lane count actually shards on a multi-device
backend and returns the same metrics as sequential dispatch.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (PlateauResult, lane_padding, plateau_threshold,
                        resolve_mode, run_packet_grid, sweep_plan)
from repro.core.sweep import (CHUNKED_MIN_LANES, SWEEP_MODES, lane_order,
                              predicted_lane_events)


class TestResolveMode:
    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="unknown sweep mode"):
            resolve_mode("warp", 222)
        with pytest.raises(ValueError, match="available"):
            resolve_mode("Fused", 222)   # case-sensitive: no silent fallback

    def test_explicit_modes_pass_through(self):
        for mode in SWEEP_MODES:
            if mode != "auto":
                assert resolve_mode(mode, 222) == mode

    def test_auto_single_device(self):
        # conftest pins tests to one CPU device: big grids chunk, small seq
        assert resolve_mode("auto", CHUNKED_MIN_LANES) == "chunked"
        assert resolve_mode("auto", 222) == "chunked"
        assert resolve_mode("auto", CHUNKED_MIN_LANES - 1) == "seq"
        assert resolve_mode("auto", 1) == "seq"

    def test_sweep_plan_provenance(self):
        plan = sweep_plan("auto", 222)
        assert plan["requested_mode"] == "auto"
        assert plan["mode"] == resolve_mode("auto", 222)
        assert plan["n_lanes"] == 222
        assert plan["n_workloads"] == 1
        assert plan["total_experiments"] == 222
        assert plan["n_devices"] >= 1
        if plan["mode"] == "chunked":
            assert plan["chunk_lanes"] >= 1

    def test_auto_counts_total_cohort_experiments(self):
        """A cohort crosses the chunked threshold on W * lanes, not lanes:
        a small grid over enough stacked workloads still batches."""
        lanes = CHUNKED_MIN_LANES // 2
        assert resolve_mode("auto", lanes, n_workloads=1) == "seq"
        assert resolve_mode("auto", lanes, n_workloads=2) == "chunked"

    def test_sweep_plan_cohort_layout(self):
        plan = sweep_plan("auto", 222, n_workloads=3)
        assert plan["n_lanes"] == 222
        assert plan["n_workloads"] == 3
        assert plan["total_experiments"] == 666

    def test_run_packet_grid_validates_mode(self, small_workload):
        with pytest.raises(ValueError, match="unknown sweep mode"):
            run_packet_grid(small_workload, ks=[1.0], s_props=[0.05],
                            mode="bogus")


class TestLegacyVmapFlags:
    def test_both_vmap_flags_rejected(self, small_workload):
        """Previously vmap_k silently won; now it is a hard error."""
        with pytest.raises(ValueError, match="mutually exclusive"):
            run_packet_grid(small_workload, ks=[1.0], s_props=[0.05],
                            vmap_k=True, vmap_s=True)

    def test_vmap_flag_plus_mode_rejected(self, small_workload):
        with pytest.raises(ValueError, match="not both"):
            run_packet_grid(small_workload, ks=[1.0], s_props=[0.05],
                            vmap_k=True, mode="seq")


class TestLaneScheduling:
    def test_predictor_monotone_in_k_and_s(self):
        """Predicted event count decreases in both k and s (large k * s
        starves groups of nodes -> few big groups)."""
        ks = np.array([0.1, 1.0, 10.0, 100.0])
        ev_k = predicted_lane_events(ks, np.full(4, 60.0))
        assert (np.diff(ev_k) < 0).all()
        s = np.array([10.0, 60.0, 600.0])
        ev_s = predicted_lane_events(np.full(3, 2.0), s)
        assert (np.diff(ev_s) < 0).all()

    def test_lane_order_is_a_permutation(self):
        k = np.array([100.0, 0.1, 2.0, 2.0])
        s = np.array([60.0, 60.0, 60.0, 10.0])
        order = lane_order(k, s)
        assert sorted(order.tolist()) == [0, 1, 2, 3]
        # longest-predicted lane (smallest k*s) first
        assert order[0] == 1
        assert order[-1] == 0

    def test_lane_padding(self):
        assert lane_padding(222, 1) == 0
        assert lane_padding(222, 2) == 0
        assert lane_padding(222, 4) == 2
        assert lane_padding(222, 8) == 2
        assert lane_padding(4, 4) == 0
        assert lane_padding(1, 4) == 3


class TestPlateauThreshold:
    def test_returns_threshold_and_plateau(self):
        ks = np.array([0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0])
        w = np.array([900.0, 500.0, 120.0, 100.0, 100.0, 100.0, 100.0, 100.0])
        res = plateau_threshold(ks, w)
        assert isinstance(res, PlateauResult)
        assert res.plateau == pytest.approx(100.0)
        # band = 0.05 * 100 + 0.031 * 100 = 8.1: the 120 cell is outside
        assert res.threshold == pytest.approx(4.0)

    def test_unsorted_input_is_sorted_not_garbage(self):
        ks = np.array([0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0])
        w = np.array([900.0, 500.0, 120.0, 100.0, 100.0, 100.0, 100.0, 100.0])
        perm = np.random.default_rng(0).permutation(len(ks))
        assert plateau_threshold(ks[perm], w[perm]) == plateau_threshold(ks, w)

    def test_short_input(self):
        res = plateau_threshold([2.0], [50.0])
        assert res == PlateauResult(2.0, 50.0)
        res = plateau_threshold([1.0, 4.0], [300.0, 100.0])
        assert res.plateau == pytest.approx(np.median([300.0, 100.0]))

    def test_bad_input_raises(self):
        with pytest.raises(ValueError, match="equal-length"):
            plateau_threshold([1.0, 2.0], [1.0, 2.0, 3.0])
        with pytest.raises(ValueError, match="at least one"):
            plateau_threshold([], [])

    def test_abs_tol_parameter(self):
        """The absolute slack is a parameter now (default: the measured
        float32 rounding envelope), not a hard-coded 1 second."""
        ks = np.array([1.0, 2.0, 4.0, 8.0, 16.0, 32.0])
        w = np.array([103.0, 100.0, 100.0, 100.0, 100.0, 100.0])
        tight = plateau_threshold(ks, w, rel_tol=0.0, abs_tol=1.0)
        loose = plateau_threshold(ks, w, rel_tol=0.0, abs_tol=10.0)
        assert tight.threshold == pytest.approx(2.0)
        assert loose.threshold == pytest.approx(1.0)
        # default slack scales with the plateau (0.031 * 100 = 3.1 s here)
        # instead of assuming second-scale waits
        default = plateau_threshold(ks, w, rel_tol=0.0)
        assert default.threshold == pytest.approx(1.0)


_SHARD_SCRIPT = r"""
import json
import numpy as np
from repro.core import ChaosConfig, group_workloads, lane_padding, \
    run_cohort_grid, run_packet_grid
from repro.core.sweep import cohort_lane_sharding, lane_sharding
from repro.workload.lublin import WorkloadParams, generate_workload

import jax
assert jax.device_count() == 4, jax.devices()

wl = generate_workload(WorkloadParams(
    n_jobs=80, nodes=32, load=0.9, homogeneous=True, seed=7))
ks, s_props = [0.5, 8.0, 100.0], [0.05, 0.5]      # 6 lanes: 6 % 4 != 0
assert lane_padding(len(ks) * len(s_props)) == 2
assert lane_sharding(8, pad=True) is not None     # padded count shards
assert lane_sharding(6) is None                   # default stays strict
assert cohort_lane_sharding(8, pad=True) is not None
assert cohort_lane_sharding(6) is None
seq = run_packet_grid(wl, ks=ks, s_props=s_props, mode="seq")
fused = run_packet_grid(wl, ks=ks, s_props=s_props, mode="fused")

# the cohort form of the same padded sharding: [W, lanes] with the lane
# axis split over the 4 devices, members bitwise-matching solo fused runs
flows = {"a": wl, "b": generate_workload(WorkloadParams(
    n_jobs=80, nodes=32, load=0.95, homogeneous=True, seed=8))}
cohort = group_workloads(flows, np.float32)[0]
grids = run_cohort_grid(cohort, ks=ks, s_props=s_props, mode="fused")
cohort_match = all(
    np.array_equal(np.asarray(getattr(grids[name], f)),
                   np.asarray(getattr(
                       run_packet_grid(w, ks=ks, s_props=s_props,
                                       mode="fused"), f)))
    for name, w in flows.items() for f in grids[name]._fields)

# the pallas step (interpret mode here) in the same per-device layout,
# fault-free and under a seeded fault grid, bitwise == the XLA step
chaos = ChaosConfig(mtbf_chip_hours=2.0, ckpt_period=120.0,
                    straggler_prob=0.3, straggler_factor=2.0,
                    straggler_deadline=1.5, lane=0, seed=11)
same = lambda a, b: all(np.array_equal(np.asarray(getattr(a, f)),
                                       np.asarray(getattr(b, f)),
                                       equal_nan=True) for f in a._fields)
pallas_match = same(fused, run_packet_grid(
    wl, ks=ks, s_props=s_props, mode="fused", step_impl="pallas"))
chaos_fused = run_packet_grid(wl, ks=ks, s_props=s_props, mode="fused",
                              chaos=chaos)
chaos_match = same(chaos_fused, run_packet_grid(
    wl, ks=ks, s_props=s_props, mode="chunked", chaos=chaos)) and same(
    chaos_fused, run_packet_grid(wl, ks=ks, s_props=s_props, mode="fused",
                                 chaos=chaos, step_impl="pallas"))

print(json.dumps({
    "seq_avg_wait": np.asarray(seq.avg_wait).tolist(),
    "fused_avg_wait": np.asarray(fused.avg_wait).tolist(),
    "fused_n_groups": np.asarray(fused.n_groups).tolist(),
    "seq_n_groups": np.asarray(seq.n_groups).tolist(),
    "fused_ok": bool(np.asarray(fused.ok).all()),
    "shape": list(np.asarray(fused.avg_wait).shape),
    "cohort_match": bool(cohort_match),
    "cohort_ok": bool(all(np.asarray(g.ok).all() for g in grids.values())),
    "pallas_match": bool(pallas_match),
    "chaos_match": bool(chaos_match),
    "chaos_requeues": int(np.asarray(chaos_fused.requeues).sum()),
}))
"""


def test_padded_sharding_multi_device_subprocess():
    """222-style non-divisible lane counts shard via sentinel padding: a
    forced 4-device CPU backend runs a 6-lane fused grid (pad 2) and must
    reproduce sequential dispatch exactly."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr}\nstdout:\n{proc.stdout}"
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["fused_ok"]
    assert out["shape"] == [3, 2]
    np.testing.assert_allclose(out["fused_avg_wait"], out["seq_avg_wait"],
                               rtol=1e-5, atol=1e-5)
    assert out["fused_n_groups"] == out["seq_n_groups"]
    assert out["cohort_ok"]
    assert out["cohort_match"]    # [W, lanes] sharded == solo fused, bitwise
    assert out["pallas_match"]    # per-device pallas step == XLA, bitwise
    assert out["chaos_match"]     # sharded fault grid == chunked, bitwise
    assert out["chaos_requeues"] > 0      # the fault path genuinely ran

"""Ahead-of-time compiles of the main-path kernel for a described TPU v5e.

No chip is attached here: the TPU compiler compiles for a v5e topology
that is only described. That catches what interpret mode cannot — a
primitive Mosaic does not lower, an unsupported layout or select — at
the widths the sweep dispatches: the chunked cohort's 56 lanes (222
lanes in 4 balanced chunks) and the fused cohort's 222, at the paper's
N=5000 jobs, H=8 types and a 100-slot ring (M=100), float32, with and
without chaos; and the fused layout's whole program with its lane axis
sharded over the described 2x2, each chip running the kernel on its
quarter of the 224 padded lanes.

The topology is described inside a module fixture and never at import,
so every test worker collects the same tests and only the one running
this file loads the TPU library. The kernel wrapper picks interpret
mode from `jax.default_backend()`, which is the CPU here; the tests
steer it to the compiled path by patching that query.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import ChaosConfig, precision, resolve_ring
from repro.core.des import (PackedWorkload, _ScanState,
                            simulate_packet_scan_lanes)
from repro.core.sweep import _packet_lanes, per_device_lanes
from repro.kernels.packet_step import ops

N_JOBS, N_TYPES, RING = 5000, 8, 100
PER_TYPE = {"head", "tail", "pool_w", "pool_oldest", "pool_code"}
PER_SLOT = {"grp_end", "grp_m", "grp_jtype", "grp_rem_w", "grp_rem_cnt",
            "grp_rem_oldest"}
INT_COLS = {"next_sub", "head", "tail", "m_free", "grp_m", "n_groups",
            "pool_code", "grp_jtype", "grp_rem_cnt", "failures",
            "straggler_kills", "requeues", "requeued_jobs"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_backend(monkeypatch):
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _workload(sharding, dtype, n=N_JOBS, h=N_TYPES):
    f = lambda *shape: _spec(shape, dtype, sharding)
    i = lambda *shape: _spec(shape, jnp.int32, sharding)
    return PackedWorkload(
        submit=f(n), work=f(n), jtype=i(n), rank=i(n), cumw=f(n),
        nodes=i(n), runtime=f(n), tj_submit=f(h, n),
        tj_prefw=f(h, n + 1), t_last_submit=f(), n_types=h, n_jobs=n)


def _state(sharding, dtype, lanes):
    def col(name):
        rows = (N_TYPES if name in PER_TYPE else
                RING if name in PER_SLOT else 1)
        dt = jnp.int32 if name in INT_COLS else dtype
        return _spec((rows, lanes), dt, sharding)
    return _ScanState(*(col(name) for name in _ScanState._fields))


@pytest.mark.parametrize("lanes", [56, 222], ids=["chunked", "fused"])
@pytest.mark.parametrize("with_chaos", [False, True],
                         ids=["faultfree", "chaos"])
def test_packet_step_compiles_through_mosaic(one_chip, compiled_backend,
                                             lanes, with_chaos):
    f32 = jnp.float32
    lane = _spec((1, lanes), f32, one_chip)
    row = _spec((N_TYPES,), f32, one_chip)
    args = [_workload(one_chip, f32), lane, lane, row, row,
            _state(one_chip, f32, lanes)]
    if with_chaos:
        stream = _spec((2 * N_JOBS, lanes), f32, one_chip)
        args += [stream, stream, (lane,) * 5]

    def step(pw, k, s, p_j, tmax_j, st, *chaos):
        return ops.fused_packet_step(pw, k, s, p_j, tmax_j, st, *chaos,
                                     r_cap=N_JOBS if chaos else 0)

    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_float64_pallas_is_refused_on_device(one_chip, compiled_backend):
    with precision.dtype_scope(np.float64):
        pw = _workload(one_chip, jnp.float64, n=64, h=2)
        lanes = _spec((4,), jnp.float64, one_chip)
        run = jax.jit(lambda pw, k, s: simulate_packet_scan_lanes(
            pw, k, s, 16, step_impl="pallas"))
        with pytest.raises(ops.PallasUnsupportedError):
            run.lower(pw, lanes, lanes)


@pytest.mark.parametrize("with_chaos", [False, True],
                         ids=["faultfree", "chaos"])
def test_fused_pallas_compiles_per_chip(topo, compiled_backend, with_chaos):
    mesh = Mesh(np.asarray(topo.devices), ("lane",))
    lane_axis = NamedSharding(mesh, P("lane"))
    replicated = NamedSharding(mesh, P())
    n_lanes = 224               # the 222-lane grid padded for four chips
    f32 = jnp.float32
    lanes = _spec((n_lanes,), f32, lane_axis)
    chaos = None
    if with_chaos:
        chaos = ChaosConfig(*(lanes,) * 5, lane=_spec((n_lanes,), jnp.int32,
                                                      lane_axis), seed=11)
    run = per_device_lanes(_packet_lanes, lane_axis, 100,
                           resolve_ring(100, N_JOBS), "pallas")
    compiled = run.lower(_workload(replicated, f32), lanes, lanes,
                         chaos).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.input_shardings[0][1].shard_shape((n_lanes,)) == (56,)


def test_roofline_knows_the_described_chip(topo):
    from benchmarks.roofline import device_peaks
    peaks = device_peaks(topo.devices[0].device_kind)
    assert peaks["peak_flops"] == 197e12 and peaks["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")

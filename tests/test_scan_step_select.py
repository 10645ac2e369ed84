"""The XLA event step reads and writes the small lane-state axes by select.

`des.packet_scan_step` touches its per-type ([H]) and ring-slot ([ring])
state only through iota-compare selects (`des._first`, `des._rows`,
`des._pick`), the form the Pallas step kernel uses too: under `vmap` a
batched gather or scatter costs per-index work on the TPU, a select does
not. This module pins three things:

  * the step's results, byte for byte, against sha256 digests of every
    DesResult field recorded before the step took this form
    (``tests/golden/scan_step_digests.json``): a select returns the
    element unchanged, so no schedule, counter or float may move;
  * the traced step holds no scatter, and gathers only from the job
    tables of `PackedWorkload` or the chaos uniform stream, never from
    the lane state;
  * the shared helpers against `jnp.argmax` / `jnp.argmin` and plain
    indexing.

Regenerate the digests only after an intentional change to what the step
computes:

    PYTHONPATH=src python tests/test_scan_step_select.py
"""
import hashlib
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr, Var

from repro.core import (PAPER_INIT_PROPS, ChaosConfig, pack_workload,
                        packet_scan_step, precision, resolve_max_requeues,
                        simulate_packet_scan)
from repro.core.des import _first, _pick, _rows, _ScanState
from repro.workload.lublin import WorkloadParams, generate_workload

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden",
                           "scan_step_digests.json")

# eight of the paper's 37 scale ratios, spread over 0.1-1000
HOMOG_KS = (0.1, 0.4, 1.0, 4.0, 10.0, 40.0, 200.0, 1000.0)


def _run_lanes(wl, ks, props, dtype, chaos=None):
    """One vmapped dispatch of `simulate_packet_scan` lanes, as a chunk of
    the chunked layout runs them; DesResult fields as numpy arrays."""
    pw = pack_workload(wl, dtype)
    k = jnp.asarray(ks, dtype)
    s = jnp.asarray([wl.init_time_for_proportion(p) for p in props], dtype)
    m = wl.params.nodes
    if chaos is None:
        fn = jax.vmap(lambda kk, ss: simulate_packet_scan(pw, kk, ss, m))
        res = jax.jit(fn)(k, s)
    else:
        fn = jax.vmap(lambda kk, ss, c: simulate_packet_scan(
            pw, kk, ss, m, chaos=c))
        res = jax.jit(fn)(k, s, chaos)
    return {f: np.asarray(getattr(res, f)) for f in res._fields}


def _homog_set():
    props = [PAPER_INIT_PROPS[i % len(PAPER_INIT_PROPS)] for i in range(8)]
    wl = generate_workload(WorkloadParams(
        n_jobs=5000, nodes=100, load=0.9, homogeneous=True, seed=21))
    return _run_lanes(wl, HOMOG_KS, props, jnp.float32)


def _hetero_set():
    wl = generate_workload(WorkloadParams(
        n_jobs=1000, nodes=500, load=0.85, homogeneous=False, seed=22))
    with precision.dtype_scope(jnp.float64):
        return _run_lanes(wl, (0.5, 3.0, 20.0, 300.0),
                          (0.05, 0.2, 0.3, 0.5), jnp.float64)


def _chaos_set():
    wl = generate_workload(WorkloadParams(
        n_jobs=400, nodes=64, load=0.9, homogeneous=True, seed=23))
    lanes = lambda v: jnp.full((4,), v, jnp.float32)
    chaos = ChaosConfig(mtbf_chip_hours=lanes(2.0), ckpt_period=lanes(120.0),
                        straggler_prob=lanes(0.3),
                        straggler_factor=lanes(2.0),
                        straggler_deadline=lanes(1.5), lane=jnp.arange(4),
                        seed=17, max_requeues=40)
    return _run_lanes(wl, (0.5, 2.0, 8.0, 50.0), (0.05, 0.1, 0.3, 0.5),
                      jnp.float32, chaos)


#: the pinned sets of lanes: 8 float32 homogeneous lanes of 5000 jobs on
#: 100 nodes; 4 float64 heterogeneous lanes of 1000 jobs on 500 nodes (ring
#: 500); 4 chaos lanes whose requeue budget runs out
LANE_SETS = {"homog_f32_5000x8": _homog_set,
             "hetero_f64_1000x4": _hetero_set,
             "chaos_f32_400x4": _chaos_set}


def digest(fields: dict) -> dict:
    """{field: 'dtype shape sha256'} of each array's bytes."""
    return {f: f"{x.dtype} {list(x.shape)} "
               f"{hashlib.sha256(np.ascontiguousarray(x).tobytes()).hexdigest()}"
            for f, x in fields.items()}


@pytest.mark.parametrize("name", sorted(LANE_SETS))
def test_digests_match_recorded(name):
    with open(GOLDEN_PATH) as f:
        want = json.load(f)["sets"][name]
    got = digest(LANE_SETS[name]())
    assert sorted(got) == sorted(want)
    off = [f for f in want if got[f] != want[f]]
    assert not off, f"{name}: fields differ from the record: {off}"


# -------------------------------------------------- the step's structure
_H_FIELDS = ("head", "tail", "pool_w", "pool_oldest", "pool_code")
_INT_FIELDS = ("next_sub", "head", "tail", "m_free", "grp_m", "n_groups",
               "pool_code", "grp_jtype", "grp_rem_cnt", "failures",
               "straggler_kills", "requeues", "requeued_jobs")


def _lane_states(n_lanes, H, ring):
    """A [n_lanes]-batched _ScanState of zeros (only shapes matter)."""
    def leaf(f):
        shape = ((H,) if f in _H_FIELDS
                 else (ring,) if f.startswith("grp_") else ())
        dt = jnp.int32 if f in _INT_FIELDS else jnp.float32
        return jnp.zeros((n_lanes,) + shape, dt)
    return _ScanState(*(leaf(f) for f in _ScanState._fields))


def _indexed_ops(jaxpr, labels, out):
    """(primitive, operand origin) of every gather / scatter in `jaxpr` and
    its nested jaxprs; an operand's origin is the label of the top-level
    input it is, or 'derived' when an op produced it."""
    for e in jaxpr.eqns:
        name = e.primitive.name
        if name == "gather" or name.startswith("scatter"):
            v = e.invars[0]
            out.append((name, labels.get(v, "derived")
                        if isinstance(v, Var) else "literal"))
        for p in e.params.values():
            sub = (p.jaxpr if isinstance(p, ClosedJaxpr)
                   else p if isinstance(p, Jaxpr) else None)
            if sub is None:
                continue
            inner = {}
            if len(sub.invars) == len(e.invars):
                inner = {iv: labels.get(ov, "derived")
                         for iv, ov in zip(sub.invars, e.invars)
                         if isinstance(ov, Var)}
            _indexed_ops(sub, inner, out)
    return out


@pytest.mark.parametrize("with_chaos", [False, True],
                         ids=["faultfree", "chaos"])
def test_vmapped_step_has_no_state_gather_or_scatter(with_chaos):
    wl = generate_workload(WorkloadParams(
        n_jobs=60, nodes=16, load=0.9, homogeneous=True, seed=1))
    pw = pack_workload(wl)
    H, ring, n_lanes = pw.n_types, 16, 3
    chaos = (ChaosConfig(mtbf_chip_hours=2.0, straggler_prob=0.3, seed=1,
                         max_requeues=5) if with_chaos else None)
    R = resolve_max_requeues(chaos, pw.n_jobs)

    def step(pw, u_all, k, s, st):
        return packet_scan_step(
            pw, k, jnp.full((H,), s), jnp.ones((H,)), jnp.full((H,), 3600.0),
            st, r_cap=R, chaos=chaos, u_all=u_all if with_chaos else None)

    u_all = jnp.zeros((n_lanes, pw.n_jobs + R, 2))
    closed = jax.make_jaxpr(jax.vmap(step, in_axes=(None, 0, 0, 0, 0)))(
        pw, u_all, jnp.ones((n_lanes,)), jnp.ones((n_lanes,)),
        _lane_states(n_lanes, H, ring))
    n_pw = len(jax.tree.leaves(pw))
    labels = {v: ("workload" if i < n_pw else "uniforms" if i == n_pw
                  else "lane")
              for i, v in enumerate(closed.jaxpr.invars)}
    ops = _indexed_ops(closed.jaxpr, labels, [])
    assert not [op for op in ops if op[0].startswith("scatter")], ops
    gathers = [origin for name, origin in ops if name == "gather"]
    assert gathers, "the job-table lookups should still be gathers"
    allowed = {"workload", "uniforms"} if with_chaos else {"workload"}
    assert set(gathers) <= allowed, ops


# ------------------------------------------------------ the shared helpers
def _bits(x):
    x = np.asarray(x)
    return x.view(np.dtype(f"u{x.dtype.itemsize}"))


def _rows_with_ties(rng, n, t):
    """[n, t] float32 columns full of ties, signed zeros and infinities."""
    pool = np.array([-np.inf, -1.5, -0.0, 0.0, 2.0, 2.0, np.inf],
                    np.float32)
    return pool[rng.integers(0, len(pool), (n, t))]


def test_first_matches_argmax_argmin_first_tie():
    rng = np.random.default_rng(0)
    x = _rows_with_ties(rng, 9, 64)
    xi = rng.integers(-3, 3, (9, 64)).astype(np.int32)
    for a in (jnp.asarray(x), jnp.asarray(xi)):
        for reduce, ref in ((jnp.max, jnp.argmax), (jnp.min, jnp.argmin)):
            want = np.asarray(ref(a, axis=0))
            # the kernel's [n, T] layout
            got = _first(a, reduce)
            assert got.shape == (1, a.shape[1])
            np.testing.assert_array_equal(np.asarray(got)[0], want)
            # one lane per column, as the vmapped step sees it
            lanes = jax.vmap(lambda r: _first(r, reduce))(a.T)
            assert lanes.shape == (a.shape[1],)
            np.testing.assert_array_equal(np.asarray(lanes), want)


def test_first_of_a_boolean_mask_is_first_true_or_zero():
    m = np.array([[0, 0, 1, 0, 1], [0, 0, 0, 0, 0]], np.int32)
    got = jax.vmap(lambda r: _first(r, jnp.max))(jnp.asarray(m))
    np.testing.assert_array_equal(np.asarray(got), [2, 0])


def test_rows_shapes_and_mask():
    r = _rows(5, jnp.int32(3))
    assert r.shape == (5,)
    np.testing.assert_array_equal(np.asarray(r), np.arange(5) == 3)
    r2 = _rows(4, jnp.asarray([[0, 3, 1]], jnp.int32))
    assert r2.shape == (4, 3)
    np.testing.assert_array_equal(np.asarray(r2),
                                  np.arange(4)[:, None] == [[0, 3, 1]])


@pytest.mark.parametrize("values", [
    np.array([-0.0, 0.0, -np.inf, np.inf, 1.5e-38, -3.4e38], np.float32),
    np.array([np.iinfo(np.int32).min, np.iinfo(np.int32).max, 0, -1, 7],
             np.int32),
], ids=["float32", "int32"])
def test_pick_returns_the_element_unchanged(values):
    x = jnp.asarray(values)
    for i in range(values.shape[0]):
        got = _pick(x, _rows(values.shape[0], jnp.int32(i)))
        assert got.shape == ()
        assert _bits(got) == _bits(values[i]), (i, got, values[i])
    # every lane at once, against plain indexing
    idx = jnp.arange(values.shape[0], dtype=jnp.int32)
    lanes = jax.vmap(lambda i: _pick(x, _rows(values.shape[0], i)))(idx)
    np.testing.assert_array_equal(_bits(lanes), _bits(values))
    np.testing.assert_array_equal(_bits(lanes),
                                  _bits(jax.vmap(lambda i: x[i])(idx)))


def test_pick_float64_extremes():
    with precision.dtype_scope(jnp.float64):
        v = np.array([-0.0, np.inf, -np.inf, 2.3e-308, 1.0 + 2.0 ** -52],
                     np.float64)
        x = jnp.asarray(v)
        for i in range(v.shape[0]):
            got = _pick(x, _rows(v.shape[0], jnp.int32(i)))
            assert _bits(got) == _bits(v[i])


def test_pick_at_argmin_is_min_of_ring():
    rng = np.random.default_rng(1)
    ring = np.where(rng.random((32, 100)) < 0.4, np.inf,
                    rng.integers(0, 50, (32, 100)).astype(np.float32))
    ring[3] = np.inf                     # an empty ring: slot 0, +inf
    x = jnp.asarray(ring.astype(np.float32))
    at = jax.vmap(lambda r: _pick(r, _rows(r.shape[0], _first(r, jnp.min))))
    got = at(x)
    np.testing.assert_array_equal(_bits(got), _bits(jnp.min(x, axis=1)))
    np.testing.assert_array_equal(
        _bits(got), _bits(jax.vmap(lambda r: r[jnp.argmin(r)])(x)))


def regenerate():
    sets = {name: build() for name, build in LANE_SETS.items()}
    payload = {"jax": jax.__version__,
               "backend": jax.default_backend(),
               "sets": {name: digest(fields)
                        for name, fields in sets.items()}}
    os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
    with open(GOLDEN_PATH, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    for name, fields in sets.items():
        print(name, "ok" if fields["ok"].all() else "NOT ok",
              "groups", fields["n_groups"].tolist(),
              "requeues", fields["requeues"].tolist())
    print(f"wrote {GOLDEN_PATH}")


if __name__ == "__main__":
    regenerate()

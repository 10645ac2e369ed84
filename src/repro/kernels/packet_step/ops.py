"""Wrapper for the fused event-step kernel.

`fused_packet_step` is the call site `repro.core.des` uses from inside
the `simulate_packet_scan_lanes(step_impl="pallas")` scan: one kernel
invocation per event for a whole [T]-lane dispatch. `step_gathers`
first reads the job-axis tables at the indices the step needs, in the
enclosing XLA program: Mosaic lowers no per-lane gather along an axis
thousands of jobs long, and every one of those indices is known from
the state at the start of the step.

On the CPU backend the kernel runs with ``interpret=True``: Pallas
discharges the body back into the enclosing XLA program, so the path is
a correctness/parity path there, not a fast one. On any other backend
it compiles through Mosaic, which has no float64: a float64 state there
raises `PallasUnsupportedError` while the step is traced, rather than
falling back to the XLA step or to interpret mode. Mosaic does not
partition a kernel across devices either; the sweep's fused layout
therefore runs it per device under `shard_map`
(`repro.core.sweep.per_device_lanes`).

Not jitted here on purpose: every caller invokes it under an enclosing
`jax.jit`/`lax.scan` trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.des import INF, _pool_decode, _resolve_remnant
from repro.kernels.packet_step.kernel import (N_STATE_COLS, ChaosGathers,
                                              StepGathers, event_step_kernel)


class PallasUnsupportedError(NotImplementedError):
    """A variant the compiled (Mosaic) kernel cannot run: a float64 state."""


def interpret_mode() -> bool:
    """Interpret mode iff the backend is the CPU; Mosaic everywhere else."""
    return jax.default_backend() == "cpu"


def step_gathers(pw, st, u1=None, u2=None):
    """The job-axis lookups of one step from the [*, T] state `st`.

    Returns ``(StepGathers, ChaosGathers | None)``; the chaos half (the
    forming group's draws from the [L_cap, T] streams `u1`/`u2`, the
    decoded pool and the finishing slot's resolved requeue remnant) is
    built iff the streams are given.
    """
    N = pw.n_jobs
    nxt = jnp.minimum(st.next_sub, N - 1)
    g = StepGathers(
        pf_tail=jnp.take_along_axis(pw.tj_prefw, st.tail, axis=1),
        pf_head=jnp.take_along_axis(pw.tj_prefw, st.head, axis=1),
        ts_head=jnp.take_along_axis(pw.tj_submit,
                                    jnp.minimum(st.head, N - 1), axis=1),
        t_sub=jnp.where(st.next_sub < N, pw.submit[nxt], INF),
        sub_j=pw.jtype[nxt])
    if u1 is None:
        return g, None
    gslot = jnp.minimum(st.n_groups, u1.shape[0] - 1)
    p_cnt, p_lo, p_frag = _pool_decode(st.pool_code, N)
    qlo = jnp.where(p_cnt > 0, p_lo, st.head)
    eslot = jnp.argmin(st.grp_end, axis=0)[None, :]

    def at_e(x):
        return jnp.take_along_axis(x, eslot, axis=0)[0]

    rem = _resolve_remnant(pw, at_e(st.grp_jtype), at_e(st.grp_rem_cnt),
                           at_e(st.grp_rem_w), at_e(st.grp_rem_oldest),
                           st.t.dtype)
    rem = [x[None, :] for x in rem]
    rem[-1] = rem[-1].astype(jnp.int32)
    cg = ChaosGathers(
        jnp.take_along_axis(u1, gslot, axis=0),
        jnp.take_along_axis(u2, gslot, axis=0),
        jnp.take_along_axis(pw.tj_prefw, qlo, axis=1),
        p_cnt, p_lo, p_frag.astype(jnp.int32), *rem)
    return g, cg


def fused_packet_step(pw, k, s, p_j, tmax_j, state, u1=None, u2=None,
                      chaos_params=None, *, r_cap: int = 0):
    """Advance every lane one event. See kernel.event_step_kernel.

    `pw` is the PackedWorkload, `k`/`s` the [1, T] lane columns, `p_j`/
    `tmax_j` the [H] per-type rows and `state` a `des._ScanState` of
    [*, T] columns; `chaos_params` is the (mtbf, ckpt_period,
    straggler_prob, straggler_factor, straggler_deadline) tuple of
    [1, T] columns, present iff `u1`/`u2` (the [L_cap, T] uniform
    streams) are. Returns ``(new_state, y)`` with `y` the 4-tuple of
    [1, T] log records.
    """
    interpret = interpret_mode()
    st_cols = list(state)
    T = st_cols[0].shape[1]
    dtype = st_cols[0].dtype
    if not interpret and np.dtype(dtype) == np.float64:
        raise PallasUnsupportedError(
            "the packet_step kernel compiles through Mosaic here, which has "
            "no float64; run float64 lanes with step_impl='xla'")
    has_chaos = u1 is not None
    g, cg = step_gathers(pw, state, u1, u2)
    inputs = [k, s, p_j[:, None], tmax_j[:, None],
              jnp.reshape(pw.t_last_submit, (1, 1)), *g]
    if has_chaos:
        inputs += [*chaos_params, *cg]
    state_off = len(inputs)
    inputs += st_cols
    out_shape = ([jax.ShapeDtypeStruct(x.shape, x.dtype)
                  for x in st_cols] +
                 [jax.ShapeDtypeStruct((1, T), jnp.int32),
                  jax.ShapeDtypeStruct((1, T), dtype),
                  jax.ShapeDtypeStruct((1, T), jnp.int32),
                  jax.ShapeDtypeStruct((1, T), dtype)])
    kernel = functools.partial(event_step_kernel,
                               n_jobs=int(pw.n_jobs),
                               r_cap=int(r_cap),
                               has_chaos=has_chaos,
                               interpret=interpret)
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        input_output_aliases={state_off + i: i
                              for i in range(N_STATE_COLS)},
        interpret=interpret,
    )(*inputs)
    new_state = type(state)(*outs[:N_STATE_COLS])
    y = tuple(outs[N_STATE_COLS:])
    return new_state, y

"""Chunked diagonal linear recurrence h_t = a_t * h_{t-1} + b_t — Pallas TPU.

The RG-LRU (and any diagonal SSM) is a first-order recurrence with
per-feature decay. A naive scan is S sequential vector ops — latency-bound
on TPU. The TPU-native form used here processes the sequence in chunks:

  within a chunk (length c), with La = cumsum(log a):
      h_t = exp(La_t) * h_0  +  sum_{s<=t} exp(La_t - La_s) * b_s
  i.e. a causal [c, c] decay-weight window applied per feature — dense
  VPU work on VMEM-resident tiles instead of S dependent steps; the carry
  h_chunk_end moves between chunks through VMEM scratch across the
  sequential innermost grid axis.

Inputs are log-decays (callers have log a analytically: RG-LRU's
log a = -c * softplus(Lambda) * r), so the kernel never takes log of a
denormal. exp(La_t - La_s) <= 1 for s <= t: always stable.

Grid: (B, D/bd, S/c) with the chunk axis innermost-sequential; feature
blocks bd are lane-aligned (128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 128
DEFAULT_BD = 256


def _lru_kernel(loga_ref, b_ref, h0_ref, o_ref, hlast_ref, carry_ref, *,
                chunk: int, use_h0: bool):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        if use_h0:
            carry_ref[...] = h0_ref[0].astype(jnp.float32)
        else:
            carry_ref[...] = jnp.zeros_like(carry_ref)

    la = loga_ref[0].astype(jnp.float32)                # [c, bd]
    b = b_ref[0].astype(jnp.float32)                    # [c, bd]
    La = jnp.cumsum(la, axis=0)                          # [c, bd]
    # W[t, s, d] = exp(La_t - La_s) for s <= t else 0
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = (ti >= si)[:, :, None]
    W = jnp.where(causal, jnp.exp(La[:, None, :] - La[None, :, :]), 0.0)
    h = (W * b[None, :, :]).sum(axis=1)                  # [c, bd]
    h = h + jnp.exp(La) * carry_ref[...][None]
    o_ref[0] = h.astype(o_ref.dtype)
    carry_ref[...] = h[-1]

    @pl.when(ic == pl.num_programs(2) - 1)
    def _flush():
        hlast_ref[0] = h[-1].astype(hlast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "bd", "interpret"))
def lru_chunked(log_a, b, h0=None, *, chunk: int = DEFAULT_CHUNK,
                bd: int = DEFAULT_BD, interpret: bool = False):
    """log_a, b: [B, S, D]; h0: optional [B, D] initial state.

    Returns (h [B, S, D], h_last [B, D])."""
    B, S, D = log_a.shape
    chunk = min(chunk, S)
    bd = min(bd, D)
    ps = (-S) % chunk
    pd = (-D) % bd
    if ps or pd:
        padnb = ((0, 0), (0, ps), (0, pd))
        log_a = jnp.pad(log_a, padnb)   # log a = 0 -> a = 1: carries state
        b = jnp.pad(b, padnb)           # b = 0: no contribution
    Sp, Dp = S + ps, D + pd
    use_h0 = h0 is not None
    if h0 is None:
        h0 = jnp.zeros((B, Dp), b.dtype)
    elif pd:
        h0 = jnp.pad(h0, ((0, 0), (0, pd)))

    grid = (B, Dp // bd, Sp // chunk)
    kern = functools.partial(_lru_kernel, chunk=chunk, use_h0=use_h0)
    h, hlast = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, chunk, bd), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, bd), lambda ib, id_, ic: (ib, id_)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, bd), lambda ib, id_, ic: (ib, id_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, Dp), b.dtype),
            jax.ShapeDtypeStruct((B, Dp), b.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bd,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, b, h0)
    return h[:, :S, :D], hlast[:, :D]

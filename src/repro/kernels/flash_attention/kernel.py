"""Blocked causal/local flash attention — Pallas TPU kernel.

TPU-native tiling: grid = (batch, kv_head, q_group, Sq/bq, Skv/bkv) with the
KV-block axis innermost. TPU grids execute sequentially, so the online-
softmax running state (m, l, acc) lives in VMEM scratch that persists across
the innermost axis; the output block is written once on the last KV step.
Block shapes are (bq, head_dim) / (bkv, head_dim) — multiples of the (8,128)
float32 VMEM tile and of the 128x128 MXU.

GQA is handled by the grid, not by materializing repeated K/V: query head
h = kv*g + gi reads K/V block kv — zero replication in HBM.

Causal/local masking is done with 2-D iota against absolute positions; KV
blocks that are fully out of window are skipped via ``@pl.when`` (the
dominant saving for the 2048-token local-attention cells).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 256
DEFAULT_BKV = 256
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int, softcap: float,
                 bq: int, bkv: int, n_kv: int, seq_q: int, seq_kv: int):
    iq = pl.program_id(3)
    ik = pl.program_id(4)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    k_pos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    # offset: query i attends to absolute kv positions <= i + (seq_kv - seq_q)
    off = seq_kv - seq_q

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip blocks that are fully masked (above the causal diagonal or out of
    # the local window)
    blk_live = True
    if causal:
        blk_live = (ik * bkv) <= (iq * bq + bq - 1 + off)
    if window > 0:
        blk_live = blk_live & ((ik * bkv + bkv - 1) >
                               (iq * bq - window + off))

    @pl.when(blk_live)
    def _step():
        q = q_ref[0, 0, 0].astype(jnp.float32)           # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bkv, hd]
        v = v_ref[0, 0].astype(jnp.float32)              # [bkv, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        mask = k_pos < seq_kv                             # padding
        if causal:
            mask &= k_pos <= q_pos + off
        if window > 0:
            mask &= k_pos > q_pos + off - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # [bq, bkv]
        alpha = jnp.exp(m_prev - m_new)                   # [bq, 1]
        l_ref[...] = l_ref[...] * alpha + p.sum(1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "bq", "bkv",
                              "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         softcap: float = 0.0, bq: int = DEFAULT_BQ,
                         bkv: int = DEFAULT_BKV, interpret: bool = False):
    """q: [B, H, Sq, hd]; k, v: [B, KV, Skv, hd]; H % KV == 0.

    Returns [B, H, Sq, hd]. Sequences are padded to block multiples
    internally; padded KV columns are masked exactly.
    """
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    bq = min(bq, max(8, Sq))
    bkv = min(bkv, max(8, Skv))
    pq, pkv = (-Sq) % bq, (-Skv) % bkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    nq, nkv = (Sq + pq) // bq, (Skv + pkv) // bkv
    qg = q.reshape(B, KV, g, Sq + pq, hd)

    grid = (B, KV, g, nq, nkv)
    kern = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bkv=bkv, n_kv=nkv, seq_q=Sq, seq_kv=Skv)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, hd),
                         lambda b, kv, gi, iq, ik: (b, kv, gi, iq, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda b, kv, gi, iq, ik: (b, kv, ik, 0)),
            pl.BlockSpec((1, 1, bkv, hd),
                         lambda b, kv, gi, iq, ik: (b, kv, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, bq, hd),
                               lambda b, kv, gi, iq, ik: (b, kv, gi, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, g, Sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max m
            pltpu.VMEM((bq, 1), jnp.float32),      # running sum l
            pltpu.VMEM((bq, hd), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, k, v)
    return out.reshape(B, H, Sq + pq, hd)[:, :, :Sq]

"""Opt-in float64 precision plumbing for the simulation core.

The DES carries every time-integral accumulator (busy/useful node-seconds,
queue-length integral) in the workload dtype — float32 by default. Long
horizons or >>5000-job workloads deserve float64, but JAX truncates
``float64`` requests to float32 whenever ``jax_enable_x64`` is off, which
would turn a precision request into a silent no-op. This module makes the
choice explicit and scoped:

  * ``dtype_scope(dtype)`` — context manager that enables x64 only while a
    float64 simulation actually runs (wraps ``jax.enable_x64``),
    restoring the previous state on exit. Float32 sessions never flip:
    entering the scope with float32 is a no-op.
  * ``canonical_dtype(dtype)`` — validates a requested simulation dtype
    against the *current* x64 state and raises a clear error instead of
    letting JAX truncate silently.

High-level drivers (``run_packet_grid``, ``run_baselines``,
``simulate_packet_host``, ``benchmarks/bench_dtype``) enter ``dtype_scope``
themselves, so ``dtype=jnp.float64`` on their signatures IS the opt-in.
Low-level entry points (``pack_workload``, ``simulate_packet``, the baseline
simulators) only *validate* — callers composing them manually wrap the whole
pack-simulate-measure pipeline in one ``dtype_scope`` so every jit trace and
array creation sees a consistent x64 state.

jit caches stay correct across scopes for free: the x64 flag is part of
JAX's trace context, so a module-level jitted function compiled under
float64 never collides with its float32 cache entry.

Measured float32-vs-float64 deviations over the paper grid live in
``benchmarks/results/BENCH_dtype.json`` (see ``benchmarks/bench_dtype.py``).
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np

SUPPORTED_DTYPES = (np.dtype(np.float32), np.dtype(np.float64))


def x64_enabled() -> bool:
    """Whether float64 is currently available (``jax_enable_x64`` on)."""
    return bool(jax.config.jax_enable_x64)


def canonical_dtype(dtype) -> np.dtype:
    """Normalize and validate a simulation dtype against the x64 state.

    Raises ValueError for non-float dtypes and for float64 requested while
    x64 is disabled — the situation where JAX would otherwise silently
    truncate every array to float32.
    """
    d = np.dtype(dtype)
    if d not in SUPPORTED_DTYPES:
        raise ValueError(
            f"simulation dtype must be float32 or float64, got {d}")
    if d == np.dtype(np.float64) and not x64_enabled():
        raise ValueError(
            "float64 simulation requested while jax_enable_x64 is off; JAX "
            "would silently truncate to float32. Wrap the call in "
            "repro.core.precision.dtype_scope(jnp.float64) (or use a "
            "high-level driver such as run_packet_grid(dtype=jnp.float64), "
            "which scopes it for you).")
    return d


@contextlib.contextmanager
def dtype_scope(dtype):
    """Scoped opt-in: enable x64 iff `dtype` is float64, restore on exit.

    Yields the validated numpy dtype. Nesting is safe; float32 scopes never
    touch the flag, so surrounding float32 sessions cannot silently flip.
    """
    d = np.dtype(dtype)
    if d not in SUPPORTED_DTYPES:
        raise ValueError(
            f"simulation dtype must be float32 or float64, got {d}")
    if d == np.dtype(np.float64) and not x64_enabled():
        with jax.enable_x64(True):
            yield d
    else:
        yield d

"""Leaky integrate-and-fire dynamics, vectorized over neurons and time.

The event-driven loop of CARLsim becomes a dense time-stepped
``jax.lax.scan`` over a (T, N) spike raster.  The membrane update itself
(decay + integrate + threshold + reset) is the per-step compute hot spot
of the profiling phase; ``repro.kernels.lif_step`` provides the Pallas TPU
kernel for it and this module is wired to use either implementation.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.lif_step import lif_step

__all__ = ["LIFParams", "lif_step_jnp", "lif_run", "lif_run_ref"]


@dataclass(frozen=True)
class LIFParams:
    """Discrete-time LIF constants (per-network, scalar-broadcast)."""

    decay: float = 0.9  # membrane leak multiplier per step: v <- decay * v
    threshold: float = 1.0  # fire when v >= threshold
    v_reset: float = 0.0  # post-spike reset potential
    refractory: int = 1  # steps a neuron stays silent after firing


def lif_step_jnp(
    v: jnp.ndarray,
    refr: jnp.ndarray,
    current: jnp.ndarray,
    params: LIFParams,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One LIF step: returns (v', refr', fired).  Pure-jnp reference.

    Mirrors `repro.kernels.lif_step.ref.lif_step_ref` (the kernel oracle).
    """
    active = refr <= 0
    v = jnp.where(active, params.decay * v + current, v)
    fired = active & (v >= params.threshold)
    v = jnp.where(fired, params.v_reset, v)
    refr = jnp.where(fired, params.refractory, jnp.maximum(refr - 1, 0))
    return v, refr, fired


def lif_run(
    weights: jnp.ndarray,
    input_drive: jnp.ndarray,
    params: LIFParams,
    *,
    use_pallas: bool = False,
    seed: int = 0,
) -> np.ndarray:
    """Run T steps of a recurrently-connected LIF population.

    Args:
      weights: (N, N) synaptic matrix; weights[i, j] = strength i -> j.
        Feedforward nets are block-superdiagonal; "random" nets are sparse
        dense-stored.
      input_drive: (T, N) external input current per step (e.g. Poisson
        encoded stimulus on the input layer, zero elsewhere).
      params: LIF constants.
      use_pallas: route the membrane update through the Pallas kernel
        (interpret mode off the TPU) instead of pure jnp.

    Returns:
      (T, N) uint8 spike raster (host numpy), equal to `lif_run_ref`'s
      whenever every sum of weights is exact in float32 (see
      `repro.snn.topology`).
    """
    raster = _lif_scan(jnp.asarray(weights), jnp.asarray(input_drive), params,
                       use_pallas, jax.default_backend() != "tpu")
    return np.asarray(raster).astype(np.uint8)


@functools.partial(jax.jit, static_argnames=("params", "use_pallas", "interpret"))
def _lif_scan(weights, input_drive, params: LIFParams, use_pallas: bool,
              interpret: bool):
    n = weights.shape[0]

    def body(carry, drive_t):
        v, refr, last_spikes = carry
        # Spikes from step t-1 arrive as current at step t (1-step synapse
        # delay).  HIGHEST keeps the TPU from rounding the weights to bf16.
        syn_current = jnp.dot(last_spikes, weights,
                              precision=jax.lax.Precision.HIGHEST)
        if use_pallas:
            v, refr, fired = lif_step(
                v, refr, syn_current + drive_t,
                decay=params.decay, threshold=params.threshold,
                v_reset=params.v_reset, refractory=params.refractory,
                backend="interpret" if interpret else "pallas",
            )
        else:
            v, refr, fired = lif_step_jnp(v, refr, syn_current + drive_t, params)
        return (v, refr, fired.astype(weights.dtype)), fired

    v0 = jnp.zeros((n,), dtype=weights.dtype)
    refr0 = jnp.zeros((n,), dtype=jnp.int32)
    s0 = jnp.zeros((n,), dtype=weights.dtype)
    _, raster = jax.lax.scan(body, (v0, refr0, s0), input_drive)
    return raster


def lif_run_ref(weights: np.ndarray, input_drive: np.ndarray,
                params: LIFParams) -> np.ndarray:
    """Host numpy reference of `lif_run`: the same recurrence, step by step."""
    n = weights.shape[0]
    weights = np.asarray(weights, dtype=np.float32)
    v = np.zeros(n, dtype=np.float32)
    refr = np.zeros(n, dtype=np.int32)
    spikes = np.zeros(n, dtype=np.float32)
    raster = np.zeros(np.shape(input_drive), dtype=np.uint8)
    decay = np.float32(params.decay)
    for t, drive_t in enumerate(np.asarray(input_drive, dtype=np.float32)):
        current = spikes @ weights + drive_t
        active = refr <= 0
        v = np.where(active, decay * v + current, v)
        fired = active & (v >= np.float32(params.threshold))
        v = np.where(fired, np.float32(params.v_reset), v)
        refr = np.where(fired, np.int32(params.refractory),
                        np.maximum(refr - 1, 0)).astype(np.int32)
        spikes = fired.astype(np.float32)
        raster[t] = fired
    return raster

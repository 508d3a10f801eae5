"""Mesh construction for the production pods.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — the pod axis
carries only DCN-class gradient reductions; ICI-class collectives stay
inside a pod.

Everything is a function (never module-level) so importing this module
does not touch jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "make_mesh_with_layout",
           "batch_axes_of"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    auto = (AxisType.Auto,) * len(axes)
    if len(devices) == n:
        return jax.make_mesh(shape, axes, axis_types=auto)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for {axes} {shape}, have {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import")
    # More devices than needed (single-pod mesh under the 512 flag).
    return jax.make_mesh(shape, axes, devices=devices[:n], axis_types=auto)


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests/examples on CPU)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_mesh_with_layout(device_order: np.ndarray, *, multi_pod: bool = False):
    """Production mesh with a SNEAP-optimized logical->physical layout
    (see repro.sharding.layout): `device_order[i]` is the physical device
    that logical position i should occupy."""
    from jax.sharding import Mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devs = np.asarray(jax.devices())[np.asarray(device_order)].reshape(shape)
    return Mesh(devs, axes)


def batch_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")

"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (smoke tests and benches must see 1 device).

Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod:  2x16x16 = 512 chips ("pod", "data", "model") — the "pod" axis
is a second data-parallel dimension spanning the (slower) inter-pod links.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)}. "
            "Set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (dryrun.py does this for you).")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def data_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def make_sweep_mesh(*, max_devices: int = None):
    """1-D ("cells",) mesh over the local devices for sweep-grid table
    builds (``cachesim.sweep.run_grid(backend="jax")``): decision cells
    are row-sharded along it, the shared view history replicated.

    Returns None with <= 1 visible device — the sweep path then runs the
    same jitted computation unsharded, so single-device CI needs no
    special casing.  CPU hosts can fake a multi-device mesh with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` set before
    any jax import.
    """
    devices = jax.devices()
    if max_devices is not None:
        devices = devices[:max_devices]
    n = len(devices)
    if n <= 1:
        return None
    return jax.make_mesh((n,), ("cells",), devices=devices,
                         axis_types=(AxisType.Auto,))

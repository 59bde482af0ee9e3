"""Production mesh construction.

Single pod: (16, 16) = ("data", "model") — 256 chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; the "pod"
axis carries pure data parallelism (and, optionally, the microbatch
pipeline of distributed/pipeline.py), with gradient all-reduce across the
slow inter-pod links — which is where gradient compression applies.

A FUNCTION, not a module constant: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """`jax.make_mesh` with Auto axes: the sharding rules constrain
    activations with `with_sharding_constraint`, which under the default
    Explicit axes would only assert."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(devices=None) -> jax.sharding.Mesh:
    """A ("data", "model") mesh over `devices` (default: every local
    device; CPU smoke tests have 1)."""
    devices = list(jax.devices() if devices is None else devices)
    return _auto_mesh((len(devices), 1), ("data", "model"), devices)


def mesh_context(mesh: jax.sharding.Mesh):
    """Context that makes `mesh` the one sharding rules resolve against."""
    return jax.set_mesh(mesh)


# TPU v5e-class hardware constants for the roofline (per chip)
PEAK_BF16_FLOPS = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_LINK_BW = 50e9              # bytes/s per link
HBM_BYTES = 16 * 1024**3        # capacity

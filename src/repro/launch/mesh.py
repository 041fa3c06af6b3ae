"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
JAX device state (the dry-run must set XLA_FLAGS before the first jax init).

Mesh shapes:
  single-pod:  (16, 16)    axes ("data", "model")   -- 256 chips
  multi-pod:   (2, 16, 16) axes ("pod", "data", "model") -- 512 chips

Stage-1 of the paper's two-stage decomposition (§2): the domain is first
split across the distributed system (this mesh), then within each chip by
the cache-conscious decomposer (stage 2, ``core.autotile``).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the model code places
    arrays through ``with_sharding_constraint`` (GSPMD), not through
    sharding-in-types, which is what ``jax.make_mesh`` defaults to."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Whatever this host actually has (smoke tests / examples)."""
    n = len(jax.devices())
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


def make_device_mesh(device):
    """A one-device ``("data", "model")`` mesh: one serving replica per
    chip, so co-resident replicas never span each other's devices."""
    return make_mesh((1, 1), ("data", "model"), devices=[device])

"""Declarative parameter specs with logical sharding axes.

Every parameter is declared as a ``ParamSpec(shape, axes, init)`` where
``axes`` names one *logical* axis per dimension ("embed", "mlp", "heads",
"vocab", "experts", "layers", ...). ``repro.dist.sharding`` maps logical
axes to mesh axes through a rules table, so the same model definition lowers
to any mesh -- the two-stage decomposition the paper advocates (cluster/mesh
level vs node/chip level) stays cleanly decoupled.

Specs live in nested dicts; leaves with a leading "layers" axis are stacked
for ``lax.scan`` over homogeneous layer blocks.
"""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | embed
    scale: float = 1.0            # multiplier on the fan-in init

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_tree_map(fn: Callable[[str, ParamSpec], Any], specs: PyTree, prefix: str = "") -> PyTree:
    """Map over a nested dict of ParamSpecs, passing the dotted path."""
    if _is_spec(specs):
        return fn(prefix, specs)
    return {
        k: spec_tree_map(fn, v, f"{prefix}.{k}" if prefix else k)
        for k, v in specs.items()
    }


def init_params(specs: PyTree, rng: jax.Array, dtype=jnp.float32) -> PyTree:
    """Initialize real arrays from specs (used by smoke tests / examples)."""

    def one(path: str, spec: ParamSpec):
        # crc32, not hash(): str hashes are salted per process, and one
        # seed must give the same weights in every process.
        key = jax.random.fold_in(rng, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dtype)
        if spec.init == "embed":
            return (jax.random.normal(key, spec.shape, dtype) * 0.02 * spec.scale)
        # fan-in scaled normal; ignore leading stack axes ("layers", "experts")
        fan_dims = [s for s, a in zip(spec.shape, spec.axes)
                    if a not in ("layers", "experts")]
        fan_in = fan_dims[0] if fan_dims else spec.shape[0]
        std = spec.scale / math.sqrt(max(1, fan_in))
        return jax.random.normal(key, spec.shape, dtype) * std

    return spec_tree_map(one, specs)


def abstract_params(specs: PyTree, dtype=jnp.float32) -> PyTree:
    """ShapeDtypeStruct pytree (for .lower() without allocation)."""
    return spec_tree_map(
        lambda _, s: jax.ShapeDtypeStruct(s.shape, dtype), specs
    )


def param_axes(specs: PyTree) -> PyTree:
    """Pytree of logical-axis tuples, matching the params pytree."""
    return spec_tree_map(lambda _, s: s.axes, specs)


def count_params(specs: PyTree) -> int:
    total = 0

    def one(_, s: ParamSpec):
        nonlocal total
        total += int(np.prod(s.shape))
        return None

    spec_tree_map(one, specs)
    return total

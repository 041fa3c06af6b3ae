"""Weights from a seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference and the
system under test start from the same numbers without the reference
taking anything the program made.  The tree is the one the family module
(``bench/families/<family>.py``) lays out in ``leaf_shapes``, in the
layout the program's model takes.

A projection is normal with std ``1/sqrt(fan_in)`` (times the scale the
family gives it), the embedding has std 0.02, the norm weights are
``1 + 0.1 N(0, 1)`` and the biases ``0.05 N(0, 1)``, so that a norm or a
bias the program dropped would show in its logits.  Each leaf has a key
of its own, folded from the seed and the leaf's name.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key that keeps every bit of a seed up to 64 bits (``PRNGKey``
    alone keeps the low 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, shape, kind, scale, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "embed":
        x = 0.02 * z
    elif kind == "norm":
        x = 1.0 + 0.1 * z
    elif kind == "bias":
        x = 0.05 * z
    else:
        x = z * (scale / math.sqrt(shape[-2]))
    return x.astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make_weights(fam, m, seed: int, dtype=jnp.bfloat16, device=None) -> dict:
    """The whole tree of the family module ``fam``'s model ``m``, made in
    one jitted call on ``device`` (the default device when None), in
    ``dtype``."""
    shapes = fam.leaf_shapes(m)
    key = seed_key(seed)

    def build(key):
        return _nest({
            path: _leaf(jax.random.fold_in(key, zlib.crc32(path.encode())
                                           & 0x7FFFFFFF),
                        shape, kind, scale, dtype)
            for path, (shape, kind, scale) in shapes.items()})

    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)

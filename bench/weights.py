"""Weights from a seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference and the
system under test start from the same numbers without the reference
taking anything the program made.  The tree is the dense decoder's, in
the layout the program's ``Model`` takes (layers stacked on a leading
axis):

    embedding (V, d)  final_norm (d,)  lm_head (d, V)
    layers/ln1, ln2 (L, d)
    layers/attn/wq (L, d, H*hd)  wk, wv (L, d, KV*hd)  wo (L, H*hd, d)
    layers/attn/bq (L, H*hd)  bk, bv (L, KV*hd)          [qkv_bias only]
    layers/ffn/wg, wi (L, d, f)  wo (L, f, d)

A projection is normal with std ``1/sqrt(fan_in)`` (an output projection
also ``1/sqrt(2L)``), the embedding has std 0.02, the norm weights are
``1 + 0.1 N(0, 1)`` and the biases ``0.05 N(0, 1)``, so that a norm or a
bias the program dropped would show in its logits.  Each leaf has a key
of its own, folded from the seed and the leaf's name.
"""

from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key that keeps every bit of a seed up to 64 bits (``PRNGKey``
    alone keeps the low 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def leaf_shapes(m) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """``path -> (shape, kind, scale)`` for the model description ``m``
    (``bench.spec.ModelDesc``)."""
    d, h, kv, hd, f, v, n = (m.d_model, m.n_heads, m.n_kv_heads, m.head_dim,
                             m.d_ff, m.vocab, m.n_layers)
    out_scale = 1.0 / math.sqrt(2 * n)
    shapes = {
        "embedding": ((v, d), "embed", 1.0),
        "final_norm": ((d,), "norm", 1.0),
        "lm_head": ((d, v), "proj", 1.0),
        "layers/ln1": ((n, d), "norm", 1.0),
        "layers/ln2": ((n, d), "norm", 1.0),
        "layers/attn/wq": ((n, d, h * hd), "proj", 1.0),
        "layers/attn/wk": ((n, d, kv * hd), "proj", 1.0),
        "layers/attn/wv": ((n, d, kv * hd), "proj", 1.0),
        "layers/attn/wo": ((n, h * hd, d), "proj", out_scale),
        "layers/ffn/wg": ((n, d, f), "proj", 1.0),
        "layers/ffn/wi": ((n, d, f), "proj", 1.0),
        "layers/ffn/wo": ((n, f, d), "proj", out_scale),
    }
    if m.qkv_bias:
        shapes["layers/attn/bq"] = ((n, h * hd), "bias", 1.0)
        shapes["layers/attn/bk"] = ((n, kv * hd), "bias", 1.0)
        shapes["layers/attn/bv"] = ((n, kv * hd), "bias", 1.0)
    return shapes


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


def make_weights(m, seed: int, dtype=jnp.bfloat16, device=None) -> dict:
    """The whole tree, made in one jitted call on ``device`` (the default
    device when None), in ``dtype``."""
    shapes = leaf_shapes(m)
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

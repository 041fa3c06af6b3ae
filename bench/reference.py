"""The plain float32 reference of a dense decoder, and its fp8 control.

It follows a configuration file as the model is run (``bench.spec.
ModelDesc``): token embedding; per layer RMSNorm, attention with rotary
embeddings over the whole head dim (rotate-half), grouped K/V heads and
optional q/k/v biases, residual, RMSNorm, SwiGLU MLP, residual; a final
RMSNorm and an untied output head.  It imports nothing of the program and
reads the weights the benchmark made (``bench.weights``).

It runs one sequence at a time, padded at the end to a whole number of
1024 tokens (causal attention never looks forward, so the padding changes
no logit that is read), with the layers under ``lax.scan`` and attention
and the MLP in blocks of rows, so a 13k-token sequence of a 7168-wide
stage fits beside its weights.  Logits are made only at the positions
that are compared.

``fp8=True`` is the control: every projection's input rows and weight
columns pass through float8 e4m3 with a scale of their own, as an fp8
serving path would compute them; attention and norms stay float32.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 1024
F8_MAX = 448.0


def _q8(x: jax.Array, axis: int) -> jax.Array:
    """``x`` rounded through float8 e4m3, scaled per slice along
    ``axis`` so that each slice's largest magnitude maps to 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, -2)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """``x`` (S, heads, hd) rotated at positions 0..S-1 (rotate-half)."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, n_kv: int, qb: int) -> jax.Array:
    """Causal softmax attention, query rows in blocks of ``qb``.
    q (S, H, hd); k, v (S, KV, hd) -> (S, H*hd)."""
    s, h, hd = q.shape
    g = h // n_kv
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(s // qb, qb, n_kv, g, hd)
    kpos = jnp.arange(s)
    hi = jax.lax.Precision.HIGHEST

    def block(args):
        i, qblk = args
        sc = jnp.einsum("qkgd,skd->kgqs", qblk, k, precision=hi) * scale
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=hi)

    out = jax.lax.map(block, (jnp.arange(s // qb), qg))
    return out.reshape(s, h * hd)


def _rows(fn, x: jax.Array, rb: int) -> jax.Array:
    """``fn`` over ``x``'s rows in blocks of ``rb``."""
    s = x.shape[0]
    y = jax.lax.map(fn, x.reshape(s // rb, rb, *x.shape[1:]))
    return y.reshape(s, *y.shape[2:])


@partial(jax.jit, static_argnames=("m", "fp8"))
def _logits(w: dict, tokens: jax.Array, at: jax.Array, m, fp8: bool):
    s = tokens.shape[0]
    h, kv, hd = m.n_heads, m.n_kv_heads, m.head_dim
    qb = max(128, min(BUCKET, 2 ** int(math.log2(2 ** 28 / (h * s)))))
    rb = BUCKET
    x = w["embedding"][tokens].astype(jnp.float32)

    def layer(x, lp):
        a = lp["attn"]
        y = _rms(x, lp["ln1"], m.norm_eps)
        q, k, vv = _mm(y, a["wq"], fp8), _mm(y, a["wk"], fp8), \
            _mm(y, a["wv"], fp8)
        if m.qkv_bias:
            q = q + a["bq"].astype(jnp.float32)
            k = k + a["bk"].astype(jnp.float32)
            vv = vv + a["bv"].astype(jnp.float32)
        q = _rope(q.reshape(s, h, hd), m.rope_theta)
        k = _rope(k.reshape(s, kv, hd), m.rope_theta)
        o = _attention(q, k, vv.reshape(s, kv, hd), kv, qb)
        x = x + _rows(lambda r: _mm(r, a["wo"], fp8), o, rb)
        f = lp["ffn"]

        def mlp(r):
            r = _rms(r, lp["ln2"], m.norm_eps)
            return _mm(jax.nn.silu(_mm(r, f["wg"], fp8)) * _mm(r, f["wi"], fp8),
                       f["wo"], fp8)

        return x + _rows(mlp, x, rb), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    y = _rms(x[at], w["final_norm"], m.norm_eps)
    return _mm(y, w["lm_head"], fp8)


def _padded(x: np.ndarray, unit: int) -> np.ndarray:
    """``x`` extended to a whole number of ``unit`` by repeating its last
    entry, so that each shape compiles once."""
    n = -(-len(x) // unit) * unit
    return np.concatenate([x, np.repeat(x[-1:], n - len(x))])


def gaps(w: dict, m, prompt: np.ndarray, served: Sequence[int],
         control: bool = False) -> Tuple[float, float]:
    """Over one request: the widest gap by which a served token's
    reference logit lies below the reference's best at its position, and
    (``control``) the widest gap of the token the fp8 control puts first.
    The second is NaN without ``control``."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = len(served)
    at = jnp.asarray(_padded(np.arange(len(prompt) - 1, len(seq),
                                       dtype=np.int32), 256))
    tok = jnp.asarray(_padded(served, 256))
    toks = jnp.asarray(_padded(seq, BUCKET))
    ref = _logits(w, toks, at, m, False)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]
    prog = float(jnp.max((best - got)[:n]))
    ctl = float("nan")
    if control:
        c = jnp.argmax(_logits(w, toks, at, m, True), axis=-1)
        pick = jnp.take_along_axis(ref, c[:, None], 1)[:, 0]
        ctl = float(jnp.max((best - pick)[:n]))
    return prog, ctl


def widest_gap(w: dict, m, requests: List[Tuple[np.ndarray, List[int]]],
               control: bool = False) -> Tuple[float, float]:
    """``gaps`` over several requests: the widest of each."""
    out = [gaps(w, m, p, s, control) for p, s in requests]
    return (max(o[0] for o in out),
            max(o[1] for o in out) if control else float("nan"))

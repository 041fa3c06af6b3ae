"""The plain float32 reference, and its fp8 control, for any family.

A family module (``bench/families/<family>.py``) gives the forward pass,
``logits(w, tokens, at, desc, fp8)``, built from the blocks here; this
file compares what was served with it.  The reference imports nothing of
the program and reads the weights the benchmark made (``bench.weights``).

It runs one sequence at a time, padded at the end to a whole number of
1024 tokens (causal attention never looks forward, so the padding changes
no logit that is read), and makes logits only at the positions that are
compared.

``fp8=True`` is the control: every projection's input rows and weight
columns pass through float8 e4m3 with a scale of their own, as an fp8
serving path would compute them (``mm``); attention and norms stay
float32.
"""

from __future__ import annotations

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


def mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """``x @ w`` in float32 at the highest precision; with ``fp8`` both
    sides first pass through ``_q8`` (rows of ``x``, columns of ``w``)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, -2)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rows(fn, x: jax.Array, rb: int) -> jax.Array:
    """``fn`` over ``x``'s rows in blocks of ``rb``."""
    s = x.shape[0]
    y = jax.lax.map(fn, x.reshape(s // rb, rb, *x.shape[1:]))
    return y.reshape(s, *y.shape[2:])


def _padded(x: np.ndarray, unit: int) -> np.ndarray:
    """``x`` extended to a whole number of ``unit`` by repeating its last
    entry, so that each shape compiles once."""
    n = -(-len(x) // unit) * unit
    return np.concatenate([x, np.repeat(x[-1:], n - len(x))])


def gaps(fam, w: dict, m, prompt: np.ndarray, served: Sequence[int],
         control: bool = False) -> Tuple[float, float]:
    """Over one request, by the forward of the family module ``fam``: the
    widest gap by which a served token's reference logit lies below the
    reference's best at its position, and (``control``) the widest gap of
    the token the fp8 control puts first.  The second is NaN without
    ``control``."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = len(served)
    at = jnp.asarray(_padded(np.arange(len(prompt) - 1, len(seq),
                                       dtype=np.int32), 256))
    tok = jnp.asarray(_padded(served, 256))
    toks = jnp.asarray(_padded(seq, BUCKET))
    ref = fam.logits(w, toks, at, m, False)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]
    prog = float(jnp.max((best - got)[:n]))
    ctl = float("nan")
    if control:
        c = jnp.argmax(fam.logits(w, toks, at, m, True), axis=-1)
        pick = jnp.take_along_axis(ref, c[:, None], 1)[:, 0]
        ctl = float(jnp.max((best - pick)[:n]))
    return prog, ctl


def widest_gap(fam, w: dict, m,
               requests: List[Tuple[np.ndarray, List[int]]],
               control: bool = False) -> Tuple[float, float]:
    """``gaps`` over several requests: the widest of each."""
    out = [gaps(fam, w, m, p, s, control) for p, s in requests]
    return (max(o[0] for o in out),
            max(o[1] for o in out) if control else float("nan"))

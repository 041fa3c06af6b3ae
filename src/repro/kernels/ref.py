"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def matmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def flash_attention_ref(
    q: jax.Array,        # (B, H, Sq, D)
    k: jax.Array,        # (B, H, Sk, D)
    v: jax.Array,        # (B, H, Sk, D)
    causal: bool = True,
) -> jax.Array:
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        qpos = jnp.arange(sq)[:, None] + (sk - sq)   # align ends
        mask = jnp.arange(sk)[None, :] <= qpos
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def paged_attention_ref(
    q: jax.Array,            # (S, H, D)  one query token per slot
    k_pages: jax.Array,      # (P, T, KV, D)  page pool, one layer
    v_pages: jax.Array,      # (P, T, KV, D)
    page_table: jax.Array,   # (S, NP) int32  physical page per logical page
    lengths: jax.Array,      # (S,) int32  valid tokens incl. the current one
    window: int = 0,
) -> jax.Array:
    """Paged decode attention, defined by gather: materialize each slot's
    logical KV stream through its page table, then grouped GQA attention
    with per-row causal/window/length masks.  ``lengths[s] == 0`` marks an
    empty slot (output row undefined -- the engine ignores it)."""
    s, h, d = q.shape
    kv = k_pages.shape[2]
    g = h // kv
    # A pool may pad the head dim to whole lanes; the pad lanes are zero.
    k = k_pages[page_table][..., :d].reshape(s, -1, kv, d)   # (S, NP*T, KV, D)
    v = v_pages[page_table][..., :d].reshape(s, -1, kv, d)
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(s, kv, g, d)
    logits = jnp.einsum("skgd,stkd->skgt", qg, k).astype(jnp.float32) * scale
    kpos = jnp.arange(k.shape[1])[None, :]            # (1, NP*T)
    qpos = lengths[:, None].astype(jnp.int32) - 1     # (S, 1)
    mask = kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("skgt,stkd->skgd", probs, v)
    return out.reshape(s, h, d)


def ssd_ref(
    x: jax.Array,        # (B, S, H, P)
    dt: jax.Array,       # (B, S, H)
    A: jax.Array,        # (H,) negative
    Bm: jax.Array,       # (B, S, N)
    Cm: jax.Array,       # (B, S, N)
) -> jax.Array:
    """Sequential SSD recurrence (the definition, O(S) steps)."""

    def step(state, inp):
        xt, dtt, bt, ct = inp                       # (B,H,P),(B,H),(B,N),(B,N)
        dec = jnp.exp(dtt * A)                      # (B,H)
        upd = jnp.einsum("bh,bhp,bn->bhpn", dtt, xt.astype(jnp.float32),
                         bt.astype(jnp.float32))
        state = state * dec[..., None, None] + upd
        y = jnp.einsum("bhpn,bn->bhp", state, ct.astype(jnp.float32))
        return state, y

    b, s, h, p = x.shape
    n = Bm.shape[-1]
    s0 = jnp.zeros((b, h, p, n), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    _, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)    # (B, S, H, P)

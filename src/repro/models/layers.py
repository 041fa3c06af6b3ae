"""Core transformer layers: norms, rotary embeddings (RoPE / M-RoPE),
GQA attention (full, sliding-window, and cache-conscious blockwise), SwiGLU
FFN, embeddings and the cross-entropy loss.

All functions are pure; parameters arrive as pytrees produced from
``repro.models.params`` specs. The blockwise attention path sizes its
blocks with the paper's decomposer (``core.autotile.plan_attention``) so
long-context attention streams VMEM-sized KV partitions -- the TPU
realization of the paper's partition streams (Fig. 2).

Every tensor-parallel projection (attention q/k/v/o, the SwiGLU FFN, the
LM head) goes through ``tp_matmul``, which routes to the overlap layer's
ring/serpentine collective matmuls when the active sharding rules request
them (DESIGN.md §5) and stays a plain einsum otherwise.  Projections that
share an input -- q/k/v, the SwiGLU wg/wi pair -- go through
``fused_column_matmul`` so ``x`` streams around the ring once per block,
not once per projection.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamSpec

# ---------------------------------------------------------------------------
# Tensor-parallel projection dispatch (DESIGN.md §5)
# ---------------------------------------------------------------------------


def tp_matmul(x: jax.Array, w: jax.Array, parallel: str) -> jax.Array:
    """Projection ``y = x @ w`` over the last dim, overlap-aware.

    When the active sharding rules request ring/serpentine collectives
    (``dist.sharding.with_collectives``), the matmul is routed through
    ``dist.overlap``'s streaming kernels so the interconnect transfer of
    the next mesh partition overlaps the current block's compute; under
    GSPMD rules, outside any ``use_mesh_rules`` context, or when the shapes
    do not divide the ring, it is a plain einsum.  ``parallel`` is the
    weight's TP orientation: "column" (n sharded -> all-gather ring) or
    "row" (k sharded -> reduce-scatter ring).
    """
    from repro.dist.overlap import overlap_matmul

    y = overlap_matmul(x, w, parallel)
    if y is None:
        y = jnp.einsum("...k,kn->...n", x, w)
    return y


def fused_column_matmul(x: jax.Array, ws) -> list:
    """Several column-parallel projections of the same ``x``, one ring.

    Under ring/serpentine rules the q/k/v (and SwiGLU wg/wi) projections
    each streamed ``x`` around the ICI ring independently; fusing them into
    ``dist.overlap.make_ag_matmul_fused`` hops the k-chunk ONCE per ring
    step and runs one dot per weight per hop, so ``x`` streams through the
    ring once per block instead of once per projection (ROADMAP overlap
    item).  Bitwise-identical to the per-weight rings (same per-column
    accumulation order); falls back to per-weight ``tp_matmul`` under
    GSPMD rules or non-dividing shapes.
    """
    from repro.dist.overlap import overlap_matmul_fused

    ys = overlap_matmul_fused(x, tuple(ws))
    if ys is None:
        return [tp_matmul(x, w, "column") for w in ws]
    return ys


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight + bias).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                             # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,           # (3, B, S): temporal / height / width
    theta: float = 1e6,
    sections: Optional[Tuple[int, int, int]] = None,
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the head_dim/2 frequency slots are split
    into (t, h, w) sections, each rotated by its own position stream."""
    d = x.shape[-1]
    if sections is None:
        # Qwen2-VL proportions (16, 24, 24) of d/2 = 64, scaled to head_dim.
        t = d // 8
        h = (d // 2 - t) // 2
        sections = (t, h, d // 2 - t - h)
    assert sum(sections) == d // 2, (sections, d)
    freqs = rope_freqs(d, theta)                              # (D/2,)
    # Select which position stream drives each frequency slot.
    sec_ids = jnp.repeat(
        jnp.arange(3), jnp.array(sections), total_repeat_length=d // 2
    )                                                         # (D/2,)
    # positions: (3, B, S) -> (B, S, D/2) by picking stream per slot.
    pos = jnp.take(positions, sec_ids, axis=0)                # (D/2, B, S)
    pos = jnp.moveaxis(pos, 0, -1).astype(jnp.float32)        # (B, S, D/2)
    angles = pos * freqs                                      # (B, S, D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, KV, D) -> (B, S, KV*n_rep, D) by broadcast (GQA)."""
    if n_rep == 1:
        return k
    b, s, kv, d = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, d))
    return k.reshape(b, s, kv * n_rep, d)


def _causal_window_mask(
    q_pos: jax.Array, k_pos: jax.Array, window: int = 0
) -> jax.Array:
    """True where attention is allowed. q_pos: (Sq,), k_pos: (Sk,)."""
    m = k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


def attn_mask(
    q_pos: jax.Array,                  # (Sq,) or (B, Sq)
    k_pos: jax.Array,                  # (Sk,) or (B, Sk)
    causal: bool = True,
    window: int = 0,
    kv_len: Optional[jax.Array] = None,   # scalar or (B,)
) -> jax.Array:
    """Per-row attention mask, shaped ``(B | 1, 1, Sq, Sk)``.

    Positions and the valid cache length may carry a leading batch dim --
    the per-slot decode path gives every sequence its own absolute
    position and ``kv_len`` -- or stay 1-D/scalar (the shared-position
    batches of training and cohort decode).  Negative ``k_pos`` marks
    empty ring-cache slots and always masks.
    """
    qp = jnp.asarray(q_pos)[..., :, None]          # (..., Sq, 1)
    kp = jnp.asarray(k_pos)[..., None, :]          # (..., 1, Sk)
    m = kp >= 0
    if causal or window:
        m = m & (kp <= qp)
        if window:
            m = m & (kp > qp - window)
    else:
        m = m & jnp.ones_like(qp, bool)            # broadcast to (.., Sq, Sk)
    if kv_len is not None:
        m = m & (kp < jnp.asarray(kv_len)[..., None, None])
    while m.ndim < 3:
        m = m[None]
    return m[:, None]                              # head axis


def full_attention(
    q: jax.Array,                  # (B, Sq, H, D)
    k: jax.Array,                  # (B, Sk, H, D)  (already GQA-repeated)
    v: jax.Array,                  # (B, Sk, H, D)
    q_pos: jax.Array,              # (Sq,) or (B, Sq) absolute positions
    k_pos: jax.Array,              # (Sk,) or (B, Sk)
    causal: bool = True,
    window: int = 0,
    kv_len: Optional[jax.Array] = None,   # valid cache length: scalar or (B,)
) -> jax.Array:
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = attn_mask(q_pos, k_pos, causal=causal, window=window,
                     kv_len=kv_len)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blockwise_attention(
    q: jax.Array,                  # (B, Sq, H, D)
    k: jax.Array,                  # (B, Sk, H, D)
    v: jax.Array,                  # (B, Sk, H, D)
    q_pos: jax.Array,
    k_pos: jax.Array,
    block_q: int,
    block_kv: int,
    causal: bool = True,
    window: int = 0,
) -> jax.Array:
    """Pure-JAX flash attention: stream KV in decomposer-sized blocks with a
    running (max, sum, acc) softmax. Never materializes (Sq, Sk) logits --
    one (block_q, block_kv) tile at a time, the paper's partition stream.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)

    nq = -(-sq // block_q)
    nk = -(-sk // block_kv)
    pq = nq * block_q - sq
    pk = nk * block_kv - sk

    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, (0, pq), constant_values=-1)
    kpos = jnp.pad(k_pos, (0, pk), constant_values=2**30)

    kb = kp.reshape(b, nk, block_kv, h, d)
    vb = vp.reshape(b, nk, block_kv, h, d)
    kposb = kpos.reshape(nk, block_kv)

    def q_block(args):
        qi, qpos_i = args                      # (B, bq, H, D), (bq,)

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, vj, kpos_j = inp
            logits = jnp.einsum("bqhd,bkhd->bhqk", qi, kj).astype(jnp.float32)
            logits *= scale
            mask = _causal_window_mask(qpos_i, kpos_j, window) if (causal or window) \
                else jnp.ones((block_q, block_kv), bool)
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qi.dtype), vj
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, block_q), jnp.float32)
        a0 = jnp.zeros((b, h, block_q, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), kposb),
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(out, 1, 2).astype(qi.dtype)   # (B, bq, H, D)

    qb = qp.reshape(b, nq, block_q, h, d)
    qposb = qpos.reshape(nq, block_q)
    outs = jax.lax.map(q_block, (jnp.moveaxis(qb, 1, 0), qposb))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * block_q, h, d)
    return out[:, :sq]


def grouped_attention(
    q: jax.Array,                  # (B, Sq, H, D)
    k: jax.Array,                  # (B, Sk, KV, D)  -- NOT repeated
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    causal: bool = True,
    window: int = 0,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """GQA attention without materializing the head-repeated K/V: the query
    heads are grouped per KV head and contracted directly against the
    (possibly sequence-sharded) cache. Numerically identical to
    repeat_kv + full_attention; avoids the (B, Sk, H, D) broadcast (15 GB
    per layer for deepseek-coder decode_32k) and the cache reshard."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, kvh, g, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    logits *= scale
    mask = attn_mask(q_pos, k_pos, causal=causal, window=window,
                     kv_len=kv_len)                 # (B|1, 1, Sq, Sk)
    logits = jnp.where(mask[:, :, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, d)


def attention_op(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    cfg: ModelConfig,
    causal: bool = True,
    kv_len: Optional[jax.Array] = None,
    blockwise_threshold: Optional[int] = None,
    tile_plan=None,
) -> jax.Array:
    """Dispatch: short sequences -> full attention; long -> blockwise with
    decomposer-chosen blocks (``tile_plan`` overrides)."""
    from repro.dist.sharding import active_rule, constrain

    if blockwise_threshold is None:
        blockwise_threshold = getattr(cfg, "attn_blockwise_threshold", 8192)
    if q.shape[1] == 1 and active_rule("kv_seq") is not None:
        # Sequence-sharded decode: grouped GQA against the sharded cache.
        k = constrain(k, ("batch", "kv_seq", None, None))
        v = constrain(v, ("batch", "kv_seq", None, None))
        return grouped_attention(q, k, v, q_pos, k_pos, causal=causal,
                                 window=cfg.sliding_window, kv_len=kv_len)
    n_rep = q.shape[2] // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    # Pin the GQA-repeated K/V to the head sharding of Q: without this,
    # GSPMD's propagation through the broadcast-reshape can leave the
    # contraction partially sharded and all-reduce full (B,H,Sq,Sk) logits
    # (observed: 541 GB/chip/step on qwen2-0.5b train_4k).
    k = constrain(k, ("batch", None, "heads", None))
    v = constrain(v, ("batch", None, "heads", None))
    sk = k.shape[1]
    if kv_len is not None or sk <= blockwise_threshold or q.shape[1] == 1:
        return full_attention(
            q, k, v, q_pos, k_pos, causal=causal,
            window=cfg.sliding_window, kv_len=kv_len,
        )
    if tile_plan is None:
        from repro.core.autotile import plan_attention
        tile_plan = plan_attention(q.shape[1], sk, q.shape[-1], dtype_bytes=2)
    return blockwise_attention(
        q, k, v, q_pos, k_pos,
        block_q=int(tile_plan.block_q), block_kv=int(tile_plan.block_kv),
        causal=causal, window=cfg.sliding_window,
    )


# ---------------------------------------------------------------------------
# Attention block (projections + rope) -- GQA family
# ---------------------------------------------------------------------------


def attention_param_specs(cfg: ModelConfig, layers: int = 0) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lead = ((layers,), ("layers",)) if layers else ((), ())
    ls, la = lead
    specs = {
        "wq": ParamSpec(ls + (d, h * hd), la + ("embed", "heads")),
        "wk": ParamSpec(ls + (d, kv * hd), la + ("embed", "heads")),
        "wv": ParamSpec(ls + (d, kv * hd), la + ("embed", "heads")),
        "wo": ParamSpec(ls + (h * hd, d), la + ("heads", "embed"), scale=1.0 / math.sqrt(2 * max(1, cfg.n_layers))),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec(ls + (h * hd,), la + ("heads",), init="zeros")
        specs["bk"] = ParamSpec(ls + (kv * hd,), la + ("heads",), init="zeros")
        specs["bv"] = ParamSpec(ls + (kv * hd,), la + ("heads",), init="zeros")
    return specs


def attention_block(
    params: dict,
    x: jax.Array,                  # (B, S, d)
    q_pos: jax.Array,
    k_pos: jax.Array,
    cfg: ModelConfig,
    cache: Optional[dict] = None,  # {"k": (B, Smax, KV, D), "v": ..., "len": ()}
    positions_3d: Optional[jax.Array] = None,
    causal: bool = True,
) -> Tuple[jax.Array, Optional[dict]]:
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = fused_column_matmul(x, (params["wq"].astype(x.dtype),
                                      params["wk"].astype(x.dtype),
                                      params["wv"].astype(x.dtype)))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    from repro.dist.sharding import constrain

    q = constrain(q.reshape(b, s, h, hd), ("batch", None, "heads", None))
    k = constrain(k.reshape(b, s, kv, hd), ("batch", None, "kv_heads", None))
    v = constrain(v.reshape(b, s, kv, hd), ("batch", None, "kv_heads", None))

    if cfg.mrope and positions_3d is not None:
        q = apply_mrope(q, positions_3d, cfg.rope_theta)
        k = apply_mrope(k, positions_3d, cfg.rope_theta)
    else:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)

    kv_len = None
    new_cache = None
    if cache is not None:
        idx = cache["len"]
        w = cache["k"].shape[1]                    # cache buffer extent
        ring = bool(cfg.sliding_window) and w <= cfg.sliding_window
        if s == 1:
            slot = jnp.mod(idx, w) if ring else idx
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
            new_cache = {"k": ck, "v": cv, "len": idx + s}
            k, v = ck.astype(x.dtype), cv.astype(x.dtype)
            j = jnp.arange(w)
            if ring:
                # Absolute position held by ring slot j (negative = empty).
                k_pos = idx - jnp.mod(idx - j, w)
            else:
                k_pos = j
                kv_len = idx + s
        else:
            # Prefill from an empty cache: attend within the prompt, then
            # store the tail (last ``w`` tokens, ring-rotated so position p
            # lives at slot p mod w).
            out = attention_op(q, k, v, q_pos, k_pos, cfg, causal=causal)
            out = out.reshape(b, s, h * hd)
            out = tp_matmul(out, params["wo"].astype(x.dtype), "row")
            if s >= w:
                tail_k, tail_v = k[:, s - w:], v[:, s - w:]
                if ring:
                    shift = (s - w) % w
                    tail_k = jnp.roll(tail_k, shift, axis=1)
                    tail_v = jnp.roll(tail_v, shift, axis=1)
                ck = tail_k.astype(cache["k"].dtype)
                cv = tail_v.astype(cache["v"].dtype)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
            return out, {"k": ck, "v": cv, "len": idx + s}

    out = attention_op(q, k, v, q_pos, k_pos, cfg, causal=causal, kv_len=kv_len)
    out = out.reshape(b, s, h * hd)
    out = tp_matmul(out, params["wo"].astype(x.dtype), "row")
    return out, new_cache


def _to_pool(x: jax.Array, pool: jax.Array) -> jax.Array:
    """K/V rows ``(..., D)`` as the pool stores them: its dtype, the head
    dim zero-padded to the pool's (``serve.pages.pool_head_dim``)."""
    pad = pool.shape[-1] - x.shape[-1]
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.astype(pool.dtype)


def paged_attention_block(
    params: dict,
    x: jax.Array,                  # (S, 1, d) -- one decode token per slot
    pos: jax.Array,                # (S,) per-slot absolute position
    cfg: ModelConfig,
    k_pool: jax.Array,             # (L, P, T, KV, D) page pool
    v_pool: jax.Array,
    layer,                         # layer index into the pool (int or traced)
    table: jax.Array,              # (S, NP) int32 page table
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-slot decode attention against the paged KV pool.

    The per-slot replacement of ``attention_block``'s decode branch: each
    row carries its own absolute position (per-seq RoPE offset) and its
    own valid length (``pos + 1`` -- the per-row kv_len mask), so slots at
    different depths decode in ONE batch.  The new token's K/V is written
    through the page table (``table[s, pos // T]`` at offset ``pos % T``;
    empty slots carry ``pos == 0`` and a null table row, so their write
    lands on the pool's reserved scratch page 0), then the Pallas paged
    kernel streams the slot's pages -- block size = the planned page.
    Returns ``(out (S, 1, d), k_pool, v_pool)``.

    Write contract under prefix sharing (DESIGN.md §11): with the radix
    cache on, a table row may map pages that OTHER rows (and the tree)
    also map.  Those shared pages sit strictly below the slot's write
    frontier -- ``table[s, pos // T]`` always resolves to a page with
    pool refcount 1 (private: freshly allocated or the CoW copy), which
    the engine asserts host-side before every decode tick.  Shared pages
    are read-only here: the kernel only ever gathers from them.
    """
    from repro.dist.sharding import active_vmem_limit
    from repro.kernels.paged_attention import paged_attention

    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = fused_column_matmul(x, (params["wq"].astype(x.dtype),
                                      params["wk"].astype(x.dtype),
                                      params["wv"].astype(x.dtype)))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)     # per-seq rope offset
    k = apply_rope(k, pos[:, None], cfg.rope_theta)

    t = k_pool.shape[2]
    page_slot = pos // t
    n_logical = table.shape[1]
    page_ids = jnp.take_along_axis(
        table, jnp.minimum(page_slot, n_logical - 1)[:, None], axis=1)[:, 0]
    # A position past the table (a table_full stall riding through the
    # batch) must land on the null page, not clamp onto the slot's last
    # live page and corrupt it.
    page_ids = jnp.where(page_slot < n_logical, page_ids, 0)
    off = pos % t
    k_pool = k_pool.at[layer, page_ids, off].set(_to_pool(k[:, 0], k_pool))
    v_pool = v_pool.at[layer, page_ids, off].set(_to_pool(v[:, 0], v_pool))

    out = paged_attention(q[:, 0], k_pool[layer], v_pool[layer], table,
                          pos + 1, window=cfg.sliding_window or 0,
                          page_tokens=t,
                          vmem_limit_bytes=active_vmem_limit())
    out = tp_matmul(out.reshape(b, s, h * hd),
                    params["wo"].astype(x.dtype), "row")
    return out, k_pool, v_pool


def paged_prefill_block(
    params: dict,
    x: jax.Array,                  # (1, C, d) -- one prompt chunk
    positions: jax.Array,          # (C,) absolute positions of the chunk
    cfg: ModelConfig,
    k_pool: jax.Array,             # (L, P, T, KV, D) page pool
    v_pool: jax.Array,
    layer,                         # layer index into the pool (int or traced)
    table_row: jax.Array,          # (NP,) int32 -- ONE slot's page table
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One prompt chunk's attention, KV written straight into pool pages.

    The chunked-prefill sibling of ``paged_attention_block``: a CHUNK of
    one slot's prompt (exact length, no padding -- the partial final
    chunk is its own jit bucket) projects q/k/v, ropes at its absolute
    ``positions``, scatters K/V through the slot's ``table_row`` (page
    ``positions // T`` at offset ``positions % T`` -- the pages the
    scheduler allocated ahead of the chunk front), and attends causally
    over everything written so far by treating each query token as a
    decode row of length ``position + 1`` in the Pallas paged kernel.
    Zero post-prefill copies: the pages ARE the prefill destination.
    Returns ``(out (1, C, d), k_pool, v_pool)``.

    Write contract under prefix sharing (DESIGN.md §11): on a radix
    prefix hit the chunk front starts AFTER the shared pages, so every
    ``positions // T`` this chunk scatters into is a refcount-1 page
    (the mid-page case writes into the slot's private CoW copy, never
    the cached original) -- asserted host-side by the engine before the
    chunk runs.  The shared prefix pages are only gathered from, through
    the same ``table_row``.
    """
    from repro.dist.sharding import active_vmem_limit
    from repro.kernels.paged_attention import paged_attention

    b, c, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = fused_column_matmul(x, (params["wq"].astype(x.dtype),
                                      params["wk"].astype(x.dtype),
                                      params["wv"].astype(x.dtype)))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, c, h, hd)
    k = k.reshape(b, c, kv, hd)
    v = v.reshape(b, c, kv, hd)
    q = apply_rope(q, positions[None, :], cfg.rope_theta)
    k = apply_rope(k, positions[None, :], cfg.rope_theta)

    t = k_pool.shape[2]
    page_slot = positions // t
    n_logical = table_row.shape[0]
    page_ids = table_row[jnp.minimum(page_slot, n_logical - 1)]
    page_ids = jnp.where(page_slot < n_logical, page_ids, 0)
    off = positions % t
    k_pool = k_pool.at[layer, page_ids, off].set(_to_pool(k[0], k_pool))
    v_pool = v_pool.at[layer, page_ids, off].set(_to_pool(v[0], v_pool))

    # Each chunk token is a "decode row" over the same table with its own
    # causal length -- the paged kernel's per-row kv_len mask does the
    # intra-chunk causal masking for free.
    table = jnp.broadcast_to(table_row[None, :], (c, n_logical))
    out = paged_attention(q[0], k_pool[layer], v_pool[layer], table,
                          positions + 1, window=cfg.sliding_window or 0,
                          page_tokens=t,
                          vmem_limit_bytes=active_vmem_limit())
    out = tp_matmul(out.reshape(b, c, h * hd),
                    params["wo"].astype(x.dtype), "row")
    return out, k_pool, v_pool


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------


def ffn_param_specs(cfg: ModelConfig, d_ff: Optional[int] = None, layers: int = 0) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ls = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    return {
        "wi": ParamSpec(ls + (d, f), la + ("embed", "mlp")),
        "wg": ParamSpec(ls + (d, f), la + ("embed", "mlp")),
        "wo": ParamSpec(ls + (f, d), la + ("mlp", "embed"), scale=1.0 / math.sqrt(2 * max(1, cfg.n_layers))),
    }


def swiglu_ffn(params: dict, x: jax.Array) -> jax.Array:
    g, u = fused_column_matmul(x, (params["wg"].astype(x.dtype),
                                   params["wi"].astype(x.dtype)))
    return tp_matmul(jax.nn.silu(g) * u, params["wo"].astype(x.dtype), "row")


# ---------------------------------------------------------------------------
# Embeddings & loss
# ---------------------------------------------------------------------------


def padded_vocab(cfg: ModelConfig, mult: int = 32) -> int:
    """Pad the vocab to a mesh-friendly multiple (Whisper's 51866 does not
    divide the 16/32-way axes). Pad logits are masked to -inf in
    ``lm_logits`` so the loss semantics are unchanged."""
    return ((cfg.vocab_size + mult - 1) // mult) * mult


def embed_param_specs(cfg: ModelConfig) -> dict:
    v = padded_vocab(cfg)
    specs = {
        "embedding": ParamSpec((v, cfg.d_model), ("vocab", "embed"), init="embed"),
        "final_norm": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, v), ("embed", "vocab"))
    return specs


def embed_tokens(params: dict, tokens: jax.Array, dtype) -> jax.Array:
    return params["embedding"].astype(dtype)[tokens]


def lm_logits(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embedding"].astype(x.dtype))
    else:
        logits = tp_matmul(x, params["lm_head"].astype(x.dtype), "column")
    if logits.shape[-1] != cfg.vocab_size:  # padded vocab: mask pad slots
        pad_mask = jnp.arange(logits.shape[-1]) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, NEG_INF)
    return logits


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       z_loss: float = 1e-4) -> jax.Array:
    """Mean token NLL in f32 (+ z-loss for logit drift control)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    loss = jnp.mean(nll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(jnp.square(lse))
    return loss

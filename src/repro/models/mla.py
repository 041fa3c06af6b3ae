"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

MLA compresses the KV cache into a rank-``kv_lora_rank`` latent plus a
shared RoPE key -- itself a *cache-size* optimization very much in the
spirit of the reproduced paper: the working set is reshaped to fit the fast
memory level. Training uses the expanded form; decoding uses the absorbed
form, attending directly over the latent cache:

  logits_h = q_nope_h @ W_ukT_h @ c  +  q_rope_h @ k_rope
  out_h    = (probs_h @ c) @ W_uv_h

so the per-token cache cost is kv_lora_rank + rope_head_dim (576 floats for
DeepSeek-V2) instead of 2 * n_heads * head_dim (32768).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import NEG_INF, apply_rope, rms_norm
from repro.models.params import ParamSpec


def mla_param_specs(cfg: ModelConfig, layers: int = 0) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.nope_head_dim + m.rope_head_dim
    ls = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    specs = {
        "wkv_a": ParamSpec(ls + (d, m.kv_lora_rank + m.rope_head_dim),
                           la + ("embed", None)),
        "kv_norm": ParamSpec(ls + (m.kv_lora_rank,), la + (None,), init="ones"),
        "wk_b": ParamSpec(ls + (m.kv_lora_rank, h, m.nope_head_dim),
                          la + (None, "heads", None)),
        "wv_b": ParamSpec(ls + (m.kv_lora_rank, h, m.v_head_dim),
                          la + (None, "heads", None)),
        "wo": ParamSpec(ls + (h, m.v_head_dim, d), la + ("heads", None, "embed"),
                        scale=1.0 / math.sqrt(2 * max(1, cfg.n_layers))),
    }
    if m.q_lora_rank:
        specs["wq_a"] = ParamSpec(ls + (d, m.q_lora_rank), la + ("embed", None))
        specs["q_norm"] = ParamSpec(ls + (m.q_lora_rank,), la + (None,), init="ones")
        specs["wq_b"] = ParamSpec(ls + (m.q_lora_rank, h, qk),
                                  la + (None, "heads", None))
    else:
        specs["wq"] = ParamSpec(ls + (d, h, qk), la + ("embed", "heads", None))
    return specs


def _project_q(params, x, cfg) -> Tuple[jax.Array, jax.Array]:
    """Returns (q_nope (B,S,H,dn), q_rope (B,S,H,dr))."""
    m = cfg.mla
    if m.q_lora_rank:
        ql = rms_norm(x @ params["wq_a"].astype(x.dtype), params["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhe->bshe", ql, params["wq_b"].astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, params["wq"].astype(x.dtype))
    return q[..., : m.nope_head_dim], q[..., m.nope_head_dim:]


def mla_attention(
    params: dict,
    x: jax.Array,                  # (B, S, d)
    q_pos: jax.Array,              # (S,)
    cfg: ModelConfig,
    cache: Optional[dict] = None,  # {"ckv": (B,Smax,R), "krope": (B,Smax,dr), "len": ()}
) -> Tuple[jax.Array, Optional[dict]]:
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)

    q_nope, q_rope = _project_q(params, x, cfg)
    q_rope = apply_rope(q_rope, q_pos, cfg.rope_theta)

    kv = x @ params["wkv_a"].astype(x.dtype)               # (B,S,R+dr)
    ckv = rms_norm(kv[..., : m.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = kv[..., m.kv_lora_rank:][:, :, None, :]       # (B,S,1,dr)
    k_rope = apply_rope(k_rope, q_pos, cfg.rope_theta)[:, :, 0]  # (B,S,dr)

    new_cache = None
    if cache is None:
        # Training / prefill: expanded form.
        k_nope = jnp.einsum("bsr,rhe->bshe", ckv, params["wk_b"].astype(x.dtype))
        v = jnp.einsum("bsr,rhe->bshe", ckv, params["wv_b"].astype(x.dtype))
        k_pos = q_pos
        logits = (
            jnp.einsum("bqhe,bkhe->bhqk", q_nope, k_nope)
            + jnp.einsum("bqhe,bke->bhqk", q_rope, k_rope)
        ).astype(jnp.float32) * scale
        mask = k_pos[None, :] <= q_pos[:, None]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhe->bqhe", probs, v)      # (B,S,H,dv)
    else:
        # Decode: absorbed form over the latent cache.
        idx = cache["len"]
        ckv_c = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), idx, axis=1)
        kr_c = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope.astype(cache["krope"].dtype), idx, axis=1)
        new_cache = {"ckv": ckv_c, "krope": kr_c, "len": idx + s}
        ckv_all = ckv_c.astype(x.dtype)                    # (B,Smax,R)
        kr_all = kr_c.astype(x.dtype)                      # (B,Smax,dr)
        # Absorb W_uk into q: (B,S,H,dn) @ (R,H,dn) -> (B,S,H,R).
        q_lat = jnp.einsum("bqhe,rhe->bqhr", q_nope, params["wk_b"].astype(x.dtype))
        logits = (
            jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv_all)
            + jnp.einsum("bqhe,bke->bhqk", q_rope, kr_all)
        ).astype(jnp.float32) * scale
        k_pos = jnp.arange(ckv_all.shape[1])
        mask = (k_pos[None, :] <= q_pos[:, None]) & (k_pos < idx + s)[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhqk,bkr->bqhr", probs, ckv_all)  # (B,S,H,R)
        out = jnp.einsum("bqhr,rhe->bqhe", o_lat, params["wv_b"].astype(x.dtype))

    y = jnp.einsum("bqhe,hed->bqd", out, params["wo"].astype(x.dtype))
    return y, new_cache


# ---------------------------------------------------------------------------
# Paged MLA: the latent cache lives in the page pool
# ---------------------------------------------------------------------------
#
# One pooled buffer ``lat`` of shape (L, P, T, 1, R + dr) stores, per token,
# concat(rms_norm(ckv), roped k_rope) -- the exact absorbed-form cache row.
# The Pallas paged kernel is reused UNCHANGED by two observations:
#
#   * logits  = q_lat @ ckv + q_rope @ k_rope = concat(q_lat, q_rope) @ lat,
#     so passing the lat pool as ``k_pages`` with query concat(q_lat, q_rope)
#     computes MLA logits.  The kernel scales by 1/sqrt(R + dr) internally
#     where MLA wants 1/sqrt(nope + rope); the query is pre-scaled by the
#     ratio to compensate.
#   * out = probs @ ckv is the first R columns of probs @ lat, so passing
#     the SAME pool as ``v_pages`` and slicing ``[..., :R]`` recovers the
#     latent output (the discarded tail is probs @ k_rope -- never needed).
#
# The single shared latent acts as one KV head (kv = 1); the kernel's
# sublane zero-padding handles n_kv % 8 != 0.


def _mla_latent_row(params, x, positions, cfg):
    """Project ``x`` to its latent-cache rows and absorbed queries.

    positions: broadcastable to (B, S).  Returns
    (q_cat (B,S,H,R+dr) pre-scaled for the paged kernel, lat (B,S,R+dr)).
    """
    m = cfg.mla
    q_nope, q_rope = _project_q(params, x, cfg)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    q_lat = jnp.einsum("bqhe,rhe->bqhr", q_nope, params["wk_b"].astype(x.dtype))

    kv = x @ params["wkv_a"].astype(x.dtype)
    ckv = rms_norm(kv[..., : m.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = kv[..., m.kv_lora_rank:][:, :, None, :]
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0]

    lat_dim = m.kv_lora_rank + m.rope_head_dim
    ratio = math.sqrt(lat_dim) / math.sqrt(m.nope_head_dim + m.rope_head_dim)
    q_cat = jnp.concatenate([q_lat, q_rope], axis=-1) * ratio
    lat = jnp.concatenate([ckv, k_rope], axis=-1)       # (B,S,R+dr)
    return q_cat, lat


def _mla_out(params, o_lat, x_dtype):
    """Latent kernel output (…,H,R) -> d_model via wv_b then wo."""
    out = jnp.einsum("bqhr,rhe->bqhe", o_lat,
                     params["wv_b"].astype(x_dtype))
    return jnp.einsum("bqhe,hed->bqd", out, params["wo"].astype(x_dtype))


def paged_mla_attention_block(
    params: dict,
    x: jax.Array,                  # (S, 1, d) -- one decode token per slot
    pos: jax.Array,                # (S,) per-slot absolute position
    cfg: ModelConfig,
    lat_pool: jax.Array,           # (L, P, T, 1, R+dr) latent page pool
    layer,
    table: jax.Array,              # (S, NP) int32 page table
) -> Tuple[jax.Array, jax.Array]:
    """Per-slot absorbed-form MLA decode against the latent page pool."""
    from repro.dist.sharding import active_vmem_limit
    from repro.kernels.paged_attention import paged_attention

    m = cfg.mla
    b, s, d = x.shape
    q_cat, lat = _mla_latent_row(params, x, pos[:, None], cfg)

    t = lat_pool.shape[2]
    page_slot = pos // t
    n_logical = table.shape[1]
    page_ids = jnp.take_along_axis(
        table, jnp.minimum(page_slot, n_logical - 1)[:, None], axis=1)[:, 0]
    page_ids = jnp.where(page_slot < n_logical, page_ids, 0)
    off = pos % t
    lat_pool = lat_pool.at[layer, page_ids, off].set(
        lat[:, 0, None, :].astype(lat_pool.dtype))

    o_lat = paged_attention(
        q_cat[:, 0], lat_pool[layer], lat_pool[layer], table, pos + 1,
        window=cfg.sliding_window or 0, page_tokens=t,
        vmem_limit_bytes=active_vmem_limit())[..., : m.kv_lora_rank]
    y = _mla_out(params, o_lat[:, None], x.dtype)
    return y, lat_pool


def paged_mla_prefill_block(
    params: dict,
    x: jax.Array,                  # (1, C, d) -- one prompt chunk
    positions: jax.Array,          # (C,)
    cfg: ModelConfig,
    lat_pool: jax.Array,           # (L, P, T, 1, R+dr)
    layer,
    table_row: jax.Array,          # (NP,) int32 -- ONE slot's page table
) -> Tuple[jax.Array, jax.Array]:
    """One prompt chunk's MLA attention, latent rows written into pages."""
    from repro.dist.sharding import active_vmem_limit
    from repro.kernels.paged_attention import paged_attention

    m = cfg.mla
    b, c, d = x.shape
    q_cat, lat = _mla_latent_row(params, x, positions[None, :], cfg)

    t = lat_pool.shape[2]
    page_slot = positions // t
    n_logical = table_row.shape[0]
    page_ids = table_row[jnp.minimum(page_slot, n_logical - 1)]
    page_ids = jnp.where(page_slot < n_logical, page_ids, 0)
    off = positions % t
    lat_pool = lat_pool.at[layer, page_ids, off].set(
        lat[0, :, None, :].astype(lat_pool.dtype))

    table = jnp.broadcast_to(table_row[None, :], (c, n_logical))
    o_lat = paged_attention(
        q_cat[0], lat_pool[layer], lat_pool[layer], table, positions + 1,
        window=cfg.sliding_window or 0, page_tokens=t,
        vmem_limit_bytes=active_vmem_limit())[..., : m.kv_lora_rank]
    y = _mla_out(params, o_lat[None], x.dtype)
    return y, lat_pool

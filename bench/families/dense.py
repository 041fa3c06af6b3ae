"""The dense decoder family: what the benchmark knows of a dense model.

A configuration file whose ``family`` is ``dense`` is read here into a
``ModelDesc``; from it come the program's ``ModelConfig``, the weight
tree, the plain float32 reference forward, the operations and bytes of
the served work, and the family's fault of a decode step that leaves its
K/V unwritten.  ``bench/spec.py`` loads this file by path; only
``model_config`` and ``state_unchanged`` import the program.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import reference
from bench.flops import Work


@dataclasses.dataclass(frozen=True)
class ModelDesc:
    """The dense decoder a configuration file describes."""

    name: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_layers: int
    rope_theta: float
    norm_eps: float
    qkv_bias: bool
    dtype: str
    max_len: int

    @classmethod
    def from_config(cls, c: dict) -> "ModelDesc":
        d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
        eps = c.get("rms_norm_eps", c.get("layer_norm_eps"))
        return cls(
            name=c["name"], d_model=d, n_heads=h,
            n_kv_heads=int(c.get("num_key_value_heads", h)),
            head_dim=int(c.get("head_dim") or d // h),
            d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
            n_layers=int(c["num_hidden_layers"]),
            rope_theta=float(c["rope_theta"]), norm_eps=float(eps),
            qkv_bias=bool(c.get("use_qkv_bias", c.get("attention_bias",
                                                      False))),
            dtype=c["dtype"], max_len=int(c["max_len"]))

    def dry(self) -> "ModelDesc":
        """The CPU rehearsal's size: the same kind of model (MHA or GQA,
        bias or none) at a width the Pallas interpreter runs in seconds."""
        kv = 4 if self.n_kv_heads == self.n_heads else 2
        return dataclasses.replace(
            self, d_model=64, n_heads=4, n_kv_heads=kv, head_dim=16,
            d_ff=128, vocab=256, n_layers=2, max_len=512)


desc = ModelDesc.from_config


def model_config(m: ModelDesc, repro_arch: str):
    """The program's ``ModelConfig`` for ``m``: its registered entry
    ``repro_arch`` with every size and option the file states."""
    from repro.configs import get_model_config

    return dataclasses.replace(
        get_model_config(repro_arch), n_layers=m.n_layers, d_model=m.d_model,
        n_heads=m.n_heads, n_kv_heads=m.n_kv_heads, d_ff=m.d_ff,
        vocab_size=m.vocab, d_head=m.head_dim, qkv_bias=m.qkv_bias,
        tie_embeddings=False, rope_theta=m.rope_theta, norm_eps=m.norm_eps,
        sliding_window=0)


def leaf_shapes(m: ModelDesc) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """``path -> (shape, kind, scale)`` of the dense decoder's tree, in the
    layout the program's ``Model`` takes (layers stacked on a leading
    axis):

        embedding (V, d)  final_norm (d,)  lm_head (d, V)
        layers/ln1, ln2 (L, d)
        layers/attn/wq (L, d, H*hd)  wk, wv (L, d, KV*hd)  wo (L, H*hd, d)
        layers/attn/bq (L, H*hd)  bk, bv (L, KV*hd)          [qkv_bias only]
        layers/ffn/wg, wi (L, d, f)  wo (L, f, d)

    An output projection is scaled by ``1/sqrt(2L)`` on top of its
    ``1/sqrt(fan_in)`` (``bench.weights``)."""
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


# -- the plain float32 reference ---------------------------------------------
#
# Token embedding; per layer RMSNorm, attention with rotary embeddings over
# the whole head dim (rotate-half), grouped K/V heads and optional q/k/v
# biases, residual, RMSNorm, SwiGLU MLP, residual; a final RMSNorm and an
# untied output head.  The layers run under ``lax.scan``, attention and the
# MLP in blocks of rows, so a 13k-token sequence of a 7168-wide stage fits
# beside its weights.


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


@partial(jax.jit, static_argnames=("m", "fp8"))
def logits(w: dict, tokens: jax.Array, at: jax.Array, m: ModelDesc,
           fp8: bool) -> jax.Array:
    """The reference's float32 logits at positions ``at`` of ``tokens``
    (padded to a whole number of ``reference.BUCKET``); ``fp8`` is the
    control (``reference.mm``)."""
    s = tokens.shape[0]
    h, kv, hd = m.n_heads, m.n_kv_heads, m.head_dim
    qb = max(128, min(reference.BUCKET,
                      2 ** int(math.log2(2 ** 28 / (h * s)))))
    rb = reference.BUCKET
    mm, rms, rows = reference.mm, reference.rms, reference.rows
    x = w["embedding"][tokens].astype(jnp.float32)

    def layer(x, lp):
        a = lp["attn"]
        y = rms(x, lp["ln1"], m.norm_eps)
        q, k, vv = mm(y, a["wq"], fp8), mm(y, a["wk"], fp8), \
            mm(y, a["wv"], fp8)
        if m.qkv_bias:
            q = q + a["bq"].astype(jnp.float32)
            k = k + a["bk"].astype(jnp.float32)
            vv = vv + a["bv"].astype(jnp.float32)
        q = _rope(q.reshape(s, h, hd), m.rope_theta)
        k = _rope(k.reshape(s, kv, hd), m.rope_theta)
        o = _attention(q, k, vv.reshape(s, kv, hd), kv, qb)
        x = x + rows(lambda r: mm(r, a["wo"], fp8), o, rb)
        f = lp["ffn"]

        def mlp(r):
            r = rms(r, lp["ln2"], m.norm_eps)
            return mm(jax.nn.silu(mm(r, f["wg"], fp8)) * mm(r, f["wi"], fp8),
                      f["wo"], fp8)

        return x + rows(mlp, x, rb), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    y = rms(x[at], w["final_norm"], m.norm_eps)
    return mm(y, w["lm_head"], fp8)


# -- operations and bytes (bench/flops.py) ------------------------------------
#
# Attention is counted once for the algorithm, whatever kernel does it: a
# decode row reads the K/V of its own context once; a prefill chunk reads
# the K/V of its context (everything up to its last token) once, for all
# its rows together.  K/V are counted at the model's head dim in the served
# dtype, never at the pool's padded width and never as a re-read.


def _matmul_params(m: ModelDesc) -> int:
    """Weights one token multiplies through in one layer."""
    return (m.d_model * (m.n_heads + 2 * m.n_kv_heads) * m.head_dim
            + m.n_heads * m.head_dim * m.d_model + 3 * m.d_model * m.d_ff)


def prefill_chunk(m: ModelDesc, start: int, c: int, last: bool,
                  dtype_bytes: int) -> Work:
    """One chunk of ``c`` tokens at positions ``start..start+c-1``; rows
    attend causally, so row ``i`` sees ``start + i + 1`` tokens."""
    h, kv, hd, n = m.n_heads, m.n_kv_heads, m.head_dim, m.n_layers
    ctx_sum = c * start + c * (c + 1) // 2
    flops = 4.0 * h * hd * ctx_sum * n
    kv_bytes = 2.0 * (start + c) * kv * hd * dtype_bytes
    qo_bytes = 2.0 * c * h * hd * dtype_bytes
    model = 2.0 * c * _matmul_params(m) * n + flops
    if last:
        model += 2.0 * m.d_model * m.vocab
    return Work(flops, (kv_bytes + qo_bytes) * n, model, c)


def decode_row(m: ModelDesc, ctx: int, dtype_bytes: int) -> Work:
    """One decode row whose query sees ``ctx`` tokens."""
    h, kv, hd, n = m.n_heads, m.n_kv_heads, m.head_dim, m.n_layers
    flops = 4.0 * h * hd * ctx * n
    byts = (2.0 * ctx * kv * hd + 2.0 * h * hd) * dtype_bytes * n
    model = 2.0 * _matmul_params(m) * n + flops + 2.0 * m.d_model * m.vocab
    return Work(flops, byts, model, 1)


# -- faults (bench/faults.py) ------------------------------------------------


def state_unchanged(patch) -> None:
    """The decode step hands back its K/V pool as it got it: no decoded
    token's K/V is ever written, and the paged kernel reads the pool
    without it."""
    from repro.dist.sharding import active_vmem_limit
    from repro.kernels.paged_attention import paged_attention
    from repro.models import layers as L

    def paged_attention_block(params, x, pos, cfg, k_pool, v_pool, layer,
                              table):
        # ``layers.paged_attention_block`` without its two pool writes.
        # Dropping the written pools after the fact would keep both the
        # old and the new pool alive, which a full pool leaves no room for.
        b, s, _ = x.shape
        h, hd = cfg.n_heads, cfg.head_dim
        q, _, _ = L.fused_column_matmul(x, (params["wq"].astype(x.dtype),
                                            params["wk"].astype(x.dtype),
                                            params["wv"].astype(x.dtype)))
        if cfg.qkv_bias:
            q = q + params["bq"].astype(x.dtype)
        q = L.apply_rope(q.reshape(b, s, h, hd), pos[:, None],
                         cfg.rope_theta)
        out = paged_attention(q[:, 0], k_pool[layer], v_pool[layer], table,
                              pos + 1, window=cfg.sliding_window or 0,
                              page_tokens=k_pool.shape[2],
                              vmem_limit_bytes=active_vmem_limit())
        out = L.tp_matmul(out.reshape(b, s, h * hd),
                          params["wo"].astype(x.dtype), "row")
        return out, k_pool, v_pool

    patch(L, "paged_attention_block", paged_attention_block)

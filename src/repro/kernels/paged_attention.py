"""Paged decode attention: gather K/V through a page table, one planned
page per grid step.

The serving engine's KV pool (``repro.serve.pages``) stores every slot's
KV stream as whole *pages* -- the VMEM-sized token runs Algorithm 1 fits
at the plan's page level -- scattered across a shared physical pool.  This
kernel is the read side: for each slot it walks the slot's page table and
streams the pages through VMEM with a running (max, sum, acc) softmax, so
the working set per grid step is exactly ``PAGE_BUFFERING`` pages -- the
kernel's block size along the KV sequence IS ``page_plan()["page_tokens"]``
(asserted), which is what makes the pool's allocation granule and the
kernel's streaming granule the same object.

Grid: ``(slots, n_logical_pages)`` with pages innermost.  The page table
and per-slot lengths ride as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``) so the index map can resolve
``table[slot, page]`` before the DMA is issued -- unallocated logical
pages point at physical page 0 (the pool's reserved null page) and are
masked off by the per-row length, exactly like padded keys in the flash
kernel.  Masks are per row: causal (``kpos <= len-1``), sliding window
(``kpos > len-1-window``), and emptiness (``len == 0`` rows produce a
fully-masked, all-zero output the engine ignores).

The kernel is strictly a GATHER: it never writes KV, so the same physical
page may appear in many slots' table rows at once.  That is what the
cross-request radix prefix cache (``repro.serve.prefix``, DESIGN.md §11)
relies on -- a shared prefix's pages are mapped read-only into every
hitting slot's table, and all KV writes happen outside this kernel
through the layer-side scatters, which the engine constrains to
refcount-1 (private or copy-on-write) pages.

Runs in interpret mode on CPU (the default off-TPU), which is how the
paged-vs-cohort token-identity tests drive it.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref, *, page_tokens: int, n_kv: int,
               n_pages: int, window: int, scale: float):
    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = len_ref[s] - 1                           # -1 on empty slots

    # Pages wholly past the row's live length are a no-op under the
    # running softmax (all-masked block: corr = 1, l/acc unchanged), so
    # skip their dot products entirely -- the table width covers the
    # plan's max_tokens bound, but per-token cost must track the LIVE
    # footprint (their DMAs all resolve to the cached null page 0).
    @pl.when(p * page_tokens <= qpos)
    def _accumulate():
        qg = q_ref[0]                              # (KV, G, D)
        k = k_ref[0]                               # (T, KV, D)
        v = v_ref[0]
        _, g, d = qg.shape
        h = n_kv * g

        # Grouped GQA contraction: query heads grouped per KV head (the
        # same (kv, g) layout as layers.grouped_attention), never
        # head-repeated.  The grouping is done outside the kernel: Mosaic
        # cannot shape-cast a packed (bf16) (H, D) tile into (KV, 1, D).
        # Sub-f32 products are exact in the f32 accumulator, and Mosaic
        # refuses an fp32 contract precision on them, so a caller's
        # default_matmul_precision only reaches f32 pages.
        prec = None if k.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
        logits = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))), precision=prec,
            preferred_element_type=jnp.float32)     # (KV, G, T)
        logits = logits.reshape(h, page_tokens) * scale

        kpos = p * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (h, page_tokens), 1)
        mask = kpos <= qpos                         # causal + length + empty
        if window:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(logits, axis=-1)[:, None]   # (H, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        pr = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(pr, axis=-1)[:, None]
        pv = jax.lax.dot_general(
            pr.reshape(n_kv, g, page_tokens).astype(v.dtype), v,
            (((2,), (0,)), ((0,), (1,))), precision=prec,
            preferred_element_type=jnp.float32)      # (KV, G, D)
        acc_ref[...] = acc_ref[...] * corr + pv.reshape(h, d)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(p == n_pages - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,            # (S, H, D)  one query token per slot
    k_pages: jax.Array,      # (P, T, KV, D)  one layer's page pool
    v_pages: jax.Array,      # (P, T, KV, D)
    page_table: jax.Array,   # (S, NP) int32
    lengths: jax.Array,      # (S,) int32   valid tokens incl. current
    window: int = 0,
    page_tokens: Optional[int] = None,
    interpret: Optional[bool] = None,
    vmem_limit_bytes: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """One decode step of attention against the paged KV pool.

    ``page_tokens`` is the plan's page size; when given it is asserted
    against the pool's second dim -- the kernel refuses to stream at any
    granule other than the planned page (the whole point of the plan).
    ``vmem_limit_bytes`` is the VMEM budget the page was planned against
    (``page_plan()["vmem_limit_bytes"]``); the kernel compiles under it, so
    a page the plan admits is a page Mosaic admits.  Without it, the
    budget a default plan gives the attached chip.  ``scale`` defaults to
    ``1/sqrt(D)``.  Returns ``(S, H, D)``.
    """
    s, h, d = q.shape
    p_total, t, n_kv, _ = k_pages.shape
    if page_tokens is not None and t != page_tokens:
        raise ValueError(
            f"pool page_tokens={t} != planned page_tokens={page_tokens}; "
            f"the kernel block must be the planned page")
    if h % n_kv != 0:
        raise ValueError(f"{h} query heads do not group over {n_kv} KV heads")

    # The pool may store the head dim padded to whole lane rows
    # (``serve.pages.pool_head_dim``): zero query lanes meet the zero pad,
    # so logits and the kept output lanes are those of the true head dim.
    dp = k_pages.shape[-1]
    if dp != d:
        out = paged_attention(
            jnp.pad(q, ((0, 0), (0, 0), (0, dp - d))), k_pages, v_pages,
            page_table, lengths, window=window, page_tokens=page_tokens,
            interpret=interpret, vmem_limit_bytes=vmem_limit_bytes,
            scale=1.0 / math.sqrt(d))
        return out[..., :d]

    # The gathered K/V block is (1, t, n_kv, d): n_kv is its sublane
    # (second-minor) dim, and Mosaic tiles it in groups of 8.  A grouped-GQA
    # head count that is not a sublane multiple must be padded explicitly --
    # zero KV heads whose (also zero-padded) query heads are sliced off the
    # output -- rather than relying on the shape happening to align.  The
    # contraction batches over the KV-head dim, so padded heads never mix
    # with real ones and real heads' outputs are bit-identical.
    if n_kv % 8:
        g = h // n_kv
        kv_pad = -(-n_kv // 8) * 8
        pad = kv_pad - n_kv
        k_pages = jnp.pad(k_pages, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_pages = jnp.pad(v_pages, ((0, 0), (0, 0), (0, pad), (0, 0)))
        qg = jnp.pad(q.reshape(s, n_kv, g, d),
                     ((0, 0), (0, pad), (0, 0), (0, 0)))
        out = paged_attention(qg.reshape(s, kv_pad * g, d), k_pages,
                              v_pages, page_table, lengths, window=window,
                              page_tokens=page_tokens, interpret=interpret,
                              vmem_limit_bytes=vmem_limit_bytes, scale=scale)
        return out.reshape(s, kv_pad, g, d)[:, :n_kv].reshape(s, h, d)

    n_pages = page_table.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if vmem_limit_bytes is None and not interpret:
        from repro.hw.tpu import chip_spec

        vmem_limit_bytes = chip_spec().usable_vmem
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # page_table, lengths
        grid=(s, n_pages),
        in_specs=[
            pl.BlockSpec((1, n_kv, h // n_kv, d),
                         lambda si, pi, tbl, ln: (si, 0, 0, 0)),
            pl.BlockSpec((1, t, n_kv, d),
                         lambda si, pi, tbl, ln: (tbl[si, pi], 0, 0, 0)),
            pl.BlockSpec((1, t, n_kv, d),
                         lambda si, pi, tbl, ln: (tbl[si, pi], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda si, pi, tbl, ln: (si, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),         # running max
            pltpu.VMEM((h, 1), jnp.float32),         # running sum
            pltpu.VMEM((h, d), jnp.float32),         # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _pa_kernel, page_tokens=t, n_kv=n_kv, n_pages=n_pages,
            window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem_limit_bytes),
        ) if not interpret else None,
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(s, n_kv, h // n_kv, d), k_pages, v_pages)

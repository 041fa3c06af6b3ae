"""Paged attention: gather K/V through a page table, one planned page per
grid step -- a decode kernel (one query row per slot) and a chunk-wide
prefill kernel (one slot's prompt chunk, ``paged_prefill_attention``).

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

The prefill kernel reads the same pages for one slot's chunk of C rows:
a tile of ``prefill_block_q`` rows meets each live page once, so a chunk
reads its context about C / block_q times (the decode kernel over a
table broadcast to C rows read it C times).  The two share only the
online-softmax update (``_softmax_step``).

Both run in interpret mode on CPU (the default off-TPU), which is how the
paged-vs-cohort token-identity tests drive them.
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


def _softmax_step(logits, m_prev, l_prev):
    """One block of the running (max, sum) softmax over the last axis:
    the block's probabilities under the new max, the factor that rescales
    what was accumulated before, and the new max and sum (``(..., 1)``)."""
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    pr = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(pr, axis=-1, keepdims=True)
    return pr, corr, m_new, l_new


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

        pr, corr, m_new, l_new = _softmax_step(logits, m_ref[...],
                                               l_ref[...])
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
        name="paged_attention",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(s, n_kv, h // n_kv, d), k_pages, v_pages)


# ---------------------------------------------------------------------------
# Chunk-wide prefill: one slot's prompt chunk against its pages
# ---------------------------------------------------------------------------


def prefill_tile_pages(tile, pos0, *, chunk: int, block_q: int,
                       page_tokens: int, window: int, n_pages: int,
                       xp=jnp):
    """``(lo, hi)``: the first and last logical page that query tile
    ``tile`` of a chunk of ``chunk`` rows at positions ``pos0..`` reads.

    ``hi`` holds the tile's last real row (rows past the chunk are
    padding); ``lo`` the first key inside its first row's window.  The
    kernel's index map clamps each grid step's page into ``[lo, hi]``, so
    a dead page repeats a neighbour's block index and the pipeline issues
    no DMA for it.  ``xp`` is ``jnp`` inside the kernel and ``numpy`` on
    the host (``prefill_page_reads``)."""
    first = pos0 + tile * block_q
    last = pos0 + xp.minimum((tile + 1) * block_q, chunk) - 1
    hi = xp.minimum(last // page_tokens, n_pages - 1)
    lo = xp.maximum(first - window + 1, 0) // page_tokens if window else 0
    return xp.minimum(lo, hi), hi


def prefill_page_index(tile, p, table, pos0, *, chunk: int, block_q: int,
                       page_tokens: int, window: int, n_pages: int,
                       xp=jnp):
    """The physical page that grid step ``(tile, p)`` of the prefill
    kernel holds: logical page ``p`` clamped into the tile's live range,
    so the steps outside it repeat a live page's block index."""
    lo, hi = prefill_tile_pages(tile, pos0, chunk=chunk, block_q=block_q,
                                page_tokens=page_tokens, window=window,
                                n_pages=n_pages, xp=xp)
    return table[xp.minimum(xp.maximum(p, lo), hi)]


def prefill_page_reads(pos0: int, chunk: int, block_q: int,
                       page_tokens: int, window: int, n_pages: int) -> int:
    """K/V pages ``paged_prefill_attention`` fetches for one layer of one
    chunk (a page's K and V block count once): each query tile's live
    pages, less one where a tile starts on the page the previous tile
    ended on (its block index does not change, so nothing is fetched)."""
    import numpy as np

    block_q = min(block_q, -(-chunk // 8) * 8)
    reads, prev = 0, -1
    for tile in range(-(-chunk // block_q)):
        lo, hi = prefill_tile_pages(
            tile, pos0, chunk=chunk, block_q=block_q,
            page_tokens=page_tokens, window=window, n_pages=n_pages, xp=np)
        reads += int(hi - lo + 1) - (int(lo) == prev)
        prev = int(hi)
    return reads


def _pp_kernel(table_ref, pos0_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref, *, page_tokens: int, block_q: int,
               group: int, chunk: int, n_pages: int, window: int,
               scale: float):
    i = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos0 = pos0_ref[0]
    lo, hi = prefill_tile_pages(i, pos0, chunk=chunk, block_q=block_q,
                                page_tokens=page_tokens, window=window,
                                n_pages=n_pages)
    first = pos0 + i * block_q                      # the tile's first row
    k0 = p * page_tokens

    @pl.when((p >= lo) & (p <= hi))
    def _accumulate():
        qg = q_ref[...]                             # (KV, M, D), M = bq*G
        k = k_ref[0]                                # (T, KV, D)
        v = v_ref[0]
        prec = None if k.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
        logits = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))), precision=prec,
            preferred_element_type=jnp.float32) * scale     # (KV, M, T)
        # Row r of the tile is query row r // G.  With kx = kpos - first,
        # kpos <= qpos is kx * G <= r, kpos > qpos - window is
        # r < (kx + window) * G, and rows past the chunk are padding.
        m = logits.shape[1]
        r = jax.lax.broadcasted_iota(jnp.int32, (m, page_tokens), 0)
        kx = (k0 - first) + jax.lax.broadcasted_iota(
            jnp.int32, (m, page_tokens), 1)
        mask = (kx * group <= r) & (r < (chunk - i * block_q) * group)
        if window:
            mask &= r < (kx + window) * group
        logits = jnp.where(mask[None], logits, NEG_INF)
        pr, corr, m_new, l_new = _softmax_step(logits, m_ref[...],
                                               l_ref[...])
        pv = jax.lax.dot_general(
            pr.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
            precision=prec, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv     # (KV, M, D)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(p == n_pages - 1)
    def _flush():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_prefill_attention(
    q: jax.Array,            # (C, H, D)  one slot's prompt chunk
    k_pages: jax.Array,      # (P, T, KV, D)  one layer's page pool
    v_pages: jax.Array,      # (P, T, KV, D)
    table_row: jax.Array,    # (NP,) int32  the slot's page table
    pos0,                    # int32 scalar: the chunk's first position
    window: int = 0,
    page_tokens: Optional[int] = None,
    block_q: Optional[int] = None,
    interpret: Optional[bool] = None,
    vmem_limit_bytes: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal attention of a prompt chunk (rows at ``pos0 .. pos0+C-1``)
    over everything the slot's pages hold up to each row.

    Grid ``(KV-head blocks, q_tiles, pages)``, pages innermost: a tile of
    ``block_q`` query rows, laid out ``(KV, block_q * G, D)``, meets each
    live page of the slot once, as one head-batched matmul with
    M = ``block_q * G`` (the decode kernel has M = G, one row).  A head
    block is ``core.plan.prefill_kv_block`` heads, one sublane tile.  So a chunk reads its context
    about ``C / block_q`` times, not ``C`` times.  Pages after a tile's
    last row or before its first row's window are skipped and keep the
    neighbouring block index (``prefill_tile_pages``), so they cost no
    DMA.  ``block_q`` is the page plan's ``prefill_block_q`` (the largest
    tile whose working set, ``core.plan.prefill_page_vmem_bytes``, fits
    the VMEM budget); without it, that search runs here against
    ``vmem_limit_bytes``.  ``pos0`` need not be a page multiple (a prefix
    hit ending mid-page).  Returns ``(C, H, D)``.
    """
    c, h, d = q.shape
    _, t, n_kv, _ = k_pages.shape
    if page_tokens is not None and t != page_tokens:
        raise ValueError(
            f"pool page_tokens={t} != planned page_tokens={page_tokens}; "
            f"the kernel block must be the planned page")
    if h % n_kv != 0:
        raise ValueError(f"{h} query heads do not group over {n_kv} KV heads")
    kw = dict(window=window, page_tokens=page_tokens, block_q=block_q,
              interpret=interpret, vmem_limit_bytes=vmem_limit_bytes)

    # The same two pads as the decode kernel: the head dim to the pool's
    # lane width, and the KV heads to a sublane multiple.
    dp = k_pages.shape[-1]
    if dp != d:
        out = paged_prefill_attention(
            jnp.pad(q, ((0, 0), (0, 0), (0, dp - d))), k_pages, v_pages,
            table_row, pos0, scale=1.0 / math.sqrt(d), **kw)
        return out[..., :d]
    if n_kv % 8:
        g = h // n_kv
        kv_pad = -(-n_kv // 8) * 8
        pad = kv_pad - n_kv
        k_pages = jnp.pad(k_pages, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_pages = jnp.pad(v_pages, ((0, 0), (0, 0), (0, pad), (0, 0)))
        qg = jnp.pad(q.reshape(c, n_kv, g, d),
                     ((0, 0), (0, pad), (0, 0), (0, 0)))
        out = paged_prefill_attention(
            qg.reshape(c, kv_pad * g, d), k_pages, v_pages, table_row, pos0,
            scale=scale, **kw)
        return out.reshape(c, kv_pad, g, d)[:, :n_kv].reshape(c, h, d)

    from repro.core.plan import prefill_block_q, prefill_kv_block
    from repro.hw.tpu import chip_spec

    g = h // n_kv
    n_pages = table_row.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if vmem_limit_bytes is None and not interpret:
        vmem_limit_bytes = chip_spec().usable_vmem
    dtype_bytes = jnp.dtype(k_pages.dtype).itemsize
    if block_q is None:
        block_q = prefill_block_q(
            c, t, n_kv, d, h, dtype_bytes,
            vmem_limit_bytes or chip_spec().usable_vmem)
    kvb = prefill_kv_block(n_kv, dtype_bytes)
    block_q = min(block_q, -(-c // 8) * 8)
    n_tiles = -(-c // block_q)
    c_pad = n_tiles * block_q
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    # (C, H, D) -> (KV, C_pad * G, D): row r*G + g of head block kv is
    # query row r, head kv*G + g.
    qg = jnp.pad(q, ((0, c_pad - c), (0, 0), (0, 0)))
    qg = qg.reshape(c_pad, n_kv, g, d).transpose(1, 0, 2, 3)
    qg = qg.reshape(n_kv, c_pad * g, d)
    m = block_q * g

    def page_index(hb, i, p, tbl, p0):
        return (prefill_page_index(i, p, tbl, p0[0], chunk=c,
                                   block_q=block_q, page_tokens=t,
                                   window=window, n_pages=n_pages),
                0, hb, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # table_row, pos0
        grid=(n_kv // kvb, n_tiles, n_pages),
        in_specs=[
            pl.BlockSpec((kvb, m, d), lambda hb, i, p, tbl, p0: (hb, i, 0)),
            pl.BlockSpec((1, t, kvb, d), page_index),
            pl.BlockSpec((1, t, kvb, d), page_index),
        ],
        out_specs=pl.BlockSpec((kvb, m, d),
                               lambda hb, i, p, tbl, p0: (hb, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvb, m, 1), jnp.float32),    # running max
            pltpu.VMEM((kvb, m, 1), jnp.float32),    # running sum
            pltpu.VMEM((kvb, m, d), jnp.float32),    # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _pp_kernel, page_tokens=t, block_q=block_q, group=g, chunk=c,
            n_pages=n_pages, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_kv, c_pad * g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem_limit_bytes),
        ) if not interpret else None,
        interpret=interpret,
        name="paged_prefill_attention",
    )(table_row.astype(jnp.int32),
      jnp.asarray(pos0, jnp.int32).reshape(1), qg, k_pages, v_pages)
    out = out.reshape(n_kv, c_pad, g, d).transpose(1, 0, 2, 3)
    return out.reshape(c_pad, h, d)[:c]

"""Pallas kernel allclose tests vs the pure-jnp oracles (interpret mode on
CPU executes the real block program). Shape/dtype sweeps per kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.autotile import AttentionTilePlan, MatmulTilePlan
from repro.kernels import flash_attention, matmul_cc, ssd_scan
from repro.kernels.ref import flash_attention_ref, matmul_ref, ssd_ref

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    # f32 tolerance reflects blocked-vs-flat summation order, not error.
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# matmul_cc
# ---------------------------------------------------------------------------

MM_SHAPES = [
    (128, 128, 128), (256, 128, 64), (64, 256, 128), (72, 130, 50),
    (8, 512, 8), (300, 100, 200),
]


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_cc_matches_ref(m, k, n, dtype):
    ka, kb = jax.random.split(jax.random.fold_in(KEY, m * k + n))
    a = jax.random.normal(ka, (m, k), dtype)
    b = jax.random.normal(kb, (k, n), dtype)
    plan = MatmulTilePlan(m=m, k=k, n=n, bm=min(64, m), bk=min(64, k),
                          bn=min(64, n), order="cc", np=1,
                          est_vmem_bytes=0, strategy="cache_conscious")
    out = matmul_cc(a, b, plan=plan, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(matmul_ref(a, b), np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("order", ["cc", "srrc"])
def test_matmul_orders_agree(order):
    a = jax.random.normal(KEY, (192, 256), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (256, 320), jnp.float32)
    plan = MatmulTilePlan(m=192, k=256, n=320, bm=64, bk=64, bn=64,
                          order=order, np=1, est_vmem_bytes=0,
                          strategy="cache_conscious")
    out = matmul_cc(a, b, plan=plan, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    m=st.integers(min_value=8, max_value=200),
    k=st.integers(min_value=8, max_value=200),
    n=st.integers(min_value=8, max_value=200),
)
def test_matmul_cc_ragged_property(m, k, n):
    a = jax.random.normal(KEY, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 7), (k, n), jnp.float32)
    plan = MatmulTilePlan(m=m, k=k, n=n, bm=32, bk=32, bn=32, order="cc",
                          np=1, est_vmem_bytes=0, strategy="cache_conscious")
    out = matmul_cc(a, b, plan=plan, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_SHAPES = [
    # (B, H, Sq, Sk, D)
    (1, 2, 128, 128, 64),
    (2, 1, 64, 256, 32),     # decode-ish: kv longer than q
    (1, 1, 100, 100, 64),    # ragged
    (1, 2, 8, 512, 128),
]


@pytest.mark.parametrize("b,h,sq,sk,d", FA_SHAPES)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, h, sq, sk, d, causal, dtype):
    kq, kk, kv = jax.random.split(jax.random.fold_in(KEY, sq * sk), 3)
    q = jax.random.normal(kq, (b, h, sq, d), dtype)
    k = jax.random.normal(kk, (b, h, sk, d), dtype)
    v = jax.random.normal(kv, (b, h, sk, d), dtype)
    plan = AttentionTilePlan(q_len=sq, kv_len=sk, head_dim=d,
                             block_q=64, block_kv=64, np=1, est_vmem_bytes=0)
    out = flash_attention(q, k, v, causal=causal, plan=plan, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_block_sweep():
    """Different decomposer block choices must not change the result."""
    q = jax.random.normal(KEY, (1, 1, 256, 64), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (1, 1, 256, 64),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (1, 1, 256, 64),
                          jnp.float32)
    ref = flash_attention_ref(q, k, v, causal=True)
    for bq, bkv in [(32, 32), (64, 128), (128, 64), (256, 256), (8, 8)]:
        plan = AttentionTilePlan(q_len=256, kv_len=256, head_dim=64,
                                 block_q=bq, block_kv=bkv, np=1,
                                 est_vmem_bytes=0)
        out = flash_attention(q, k, v, causal=True, plan=plan, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5, err_msg=f"{bq}x{bkv}")


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_SHAPES = [
    # (B, S, H, P, N, chunk)
    (1, 64, 2, 16, 16, 16),
    (2, 128, 1, 32, 16, 32),
    (1, 100, 2, 16, 8, 32),   # ragged seq vs chunk
    (1, 64, 4, 64, 64, 64),
]


@pytest.mark.parametrize("b,s,h,p,n,chunk", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_sequential_ref(b, s, h, p, n, chunk, dtype):
    keys = jax.random.split(jax.random.fold_in(KEY, s * p), 5)
    x = jax.random.normal(keys[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h),
                                           jnp.float32)) * 0.5
    A = -jnp.exp(jax.random.normal(keys[2], (h,), jnp.float32) * 0.3)
    Bm = jax.random.normal(keys[3], (b, s, n), dtype)
    Cm = jax.random.normal(keys[4], (b, s, n), dtype)
    out = ssd_scan(x, dt.astype(dtype), A, Bm, Cm, chunk=chunk,
                   interpret=True)
    ref = ssd_ref(x.astype(jnp.float32), dt, A, Bm.astype(jnp.float32),
                  Cm.astype(jnp.float32))
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_ssd_chunk_invariance():
    """Chunk size is a pure performance knob: results must not move."""
    b, s, h, p, n = 1, 128, 2, 16, 16
    keys = jax.random.split(KEY, 5)
    x = jax.random.normal(keys[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(keys[2], (h,)) * 0.3)
    Bm = jax.random.normal(keys[3], (b, s, n))
    Cm = jax.random.normal(keys[4], (b, s, n))
    outs = [
        np.asarray(ssd_scan(x, dt, A, Bm, Cm, chunk=c, interpret=True))
        for c in (16, 32, 64, 128)
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Model-layer chunked implementations vs the same oracles
# ---------------------------------------------------------------------------

def test_model_ssd_chunked_matches_ref():
    from repro.models.mamba2 import ssd_chunked

    b, s, h, p, n = 2, 96, 2, 16, 16
    keys = jax.random.split(jax.random.fold_in(KEY, 99), 5)
    x = jax.random.normal(keys[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(keys[2], (h,)) * 0.3)
    Bm = jax.random.normal(keys[3], (b, s, n))
    Cm = jax.random.normal(keys[4], (b, s, n))
    y, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# paged attention (repro.serve.pages read side, DESIGN.md §8)
# ---------------------------------------------------------------------------


def _paged_case(seed, s, h, kv, d, t, p_total, n_logical, max_len,
                dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((p_total, t, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((p_total, t, kv, d)), dtype)
    table = jnp.asarray(rng.integers(1, p_total, (s, n_logical)), jnp.int32)
    lengths = jnp.asarray(rng.integers(0, max_len + 1, (s,)), jnp.int32)
    return q, k, v, table, lengths


@pytest.mark.parametrize("window", [0, 6, 16])
@pytest.mark.parametrize("s,h,kv,d,t", [(3, 4, 2, 16, 8), (2, 4, 4, 32, 16),
                                        (4, 8, 2, 16, 8)])
def test_paged_attention_matches_ref(s, h, kv, d, t, window):
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref

    n_logical = 3
    q, k, v, table, lengths = _paged_case(
        s * 31 + window, s, h, kv, d, t, p_total=7, n_logical=n_logical,
        max_len=n_logical * t)
    out = paged_attention(q, k, v, table, lengths, window=window,
                          page_tokens=t)
    ref = paged_attention_ref(q, k, v, table, lengths, window=window)
    live = np.asarray(lengths) > 0      # empty slots: output is undefined
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref, np.float32)[live],
                               rtol=1e-4, atol=1e-4)
    assert np.isfinite(np.asarray(out)).all()   # empty rows stay finite


def test_paged_attention_matches_dense_gather():
    """Gathering through a scrambled page table equals dense attention over
    the same logical KV stream (per-row lengths as kv_len masks)."""
    from repro.kernels.paged_attention import paged_attention
    from repro.models.layers import grouped_attention

    s, h, kv, d, t, n_logical = 3, 4, 2, 16, 8, 4
    rng = np.random.default_rng(7)
    kd = jnp.asarray(rng.standard_normal((s, n_logical * t, kv, d)),
                     jnp.float32)
    vd = jnp.asarray(rng.standard_normal((s, n_logical * t, kv, d)),
                     jnp.float32)
    q = jnp.asarray(rng.standard_normal((s, 1, h, d)), jnp.float32)
    lengths = jnp.asarray([5, 17, 32], jnp.int32)
    # Scatter each slot's stream into a scrambled pool.
    perm = rng.permutation(np.arange(1, 1 + s * n_logical))
    table = jnp.asarray(perm.reshape(s, n_logical), jnp.int32)
    pool_k = jnp.zeros((1 + s * n_logical, t, kv, d), jnp.float32)
    pool_v = jnp.zeros_like(pool_k)
    pool_k = pool_k.at[table.reshape(-1)].set(
        kd.reshape(s * n_logical, t, kv, d))
    pool_v = pool_v.at[table.reshape(-1)].set(
        vd.reshape(s * n_logical, t, kv, d))
    out = paged_attention(q[:, 0], pool_k, pool_v, table, lengths,
                          page_tokens=t)
    # Dense reference: per-row q_pos = lengths - 1, per-row kv_len mask.
    ref = grouped_attention(
        q, kd, vd, (lengths - 1)[:, None], jnp.arange(n_logical * t),
        causal=True, kv_len=lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref[:, 0]),
                               rtol=1e-4, atol=1e-4)


def test_paged_attention_refuses_wrong_page_size():
    from repro.kernels.paged_attention import paged_attention

    q, k, v, table, lengths = _paged_case(0, 2, 4, 2, 16, 8, 5, 2, 16)
    with pytest.raises(ValueError, match="planned page"):
        paged_attention(q, k, v, table, lengths, page_tokens=16)


@pytest.mark.parametrize("kv,group", [(3, 2), (5, 1), (6, 4)])
def test_paged_attention_gqa_sublane_pad(kv, group):
    """Grouped-GQA head counts that are not a sublane multiple (8) go
    through the explicit zero-pad path: the K/V pool's head dim is padded
    up to 8 and the padded heads sliced off, with outputs identical to the
    reference (the padded heads never mix into real ones)."""
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref

    h = kv * group
    s, d, t, n_logical = 3, 16, 8, 3
    q, k, v, table, lengths = _paged_case(
        kv * 11 + group, s, h, kv, d, t, p_total=7, n_logical=n_logical,
        max_len=n_logical * t)
    assert kv % 8 != 0     # the case under test
    out = paged_attention(q, k, v, table, lengths, page_tokens=t)
    ref = paged_attention_ref(q, k, v, table, lengths)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref, np.float32)[live],
                               rtol=1e-4, atol=1e-4)
    assert out.shape == (s, h, d)       # padded heads sliced back off


def _prefill_case(seed, c, h, kv, d, dp, t, n_logical, dtype=jnp.float32):
    """A chunk's queries ``(C, H, D)`` and a pool whose head dim is stored
    at ``dp`` lanes (zero beyond ``d``), under a scrambled table row."""
    rng = np.random.default_rng(seed)
    p_total = 2 * n_logical + 1
    q = jnp.asarray(rng.standard_normal((c, h, d)), dtype)
    k = np.zeros((p_total, t, kv, dp), np.float32)
    v = np.zeros((p_total, t, kv, dp), np.float32)
    k[..., :d] = rng.standard_normal((p_total, t, kv, d))
    v[..., :d] = rng.standard_normal((p_total, t, kv, d))
    table_row = jnp.asarray(rng.permutation(np.arange(1, p_total))[:n_logical],
                            jnp.int32)
    return q, jnp.asarray(k, dtype), jnp.asarray(v, dtype), table_row


@pytest.mark.parametrize(
    "c,h,kv,d,dp,t,pos0,block_q,window,latent", [
        (40, 8, 8, 64, 128, 16, 0, 16, 0, False),   # MHA, lanes 64 -> 128
        (24, 12, 3, 32, 32, 8, 8, 8, 0, False),     # GQA, 3 KV heads
        (20, 4, 2, 16, 16, 8, 13, 8, 0, False),     # pos0 mid-page
        (21, 4, 2, 16, 16, 8, 16, 8, 0, False),     # C % block_q != 0
        (16, 4, 2, 16, 16, 8, 24, 8, 10, False),    # window inside the table
        (24, 8, 1, 40, 128, 8, 5, 16, 0, True),     # MLA latent: K = V
    ])
def test_paged_prefill_matches_ref(c, h, kv, d, dp, t, pos0, block_q,
                                   window, latent):
    """The chunk-wide prefill kernel equals the decode reference over the
    table broadcast to every chunk row, each row at its own length."""
    from repro.kernels.paged_attention import paged_prefill_attention
    from repro.kernels.ref import paged_attention_ref

    n_logical = -(-(pos0 + c) // t) + 1            # one page past the chunk
    q, k, v, table_row = _prefill_case(
        c * 7 + pos0, c, h, kv, d, dp, t, n_logical)
    if latent:
        v = k
    out = paged_prefill_attention(q, k, v, table_row, pos0, window=window,
                                  page_tokens=t, block_q=block_q)
    table = jnp.broadcast_to(table_row[None], (c, n_logical))
    lengths = pos0 + 1 + jnp.arange(c, dtype=jnp.int32)
    ref = paged_attention_ref(q, k, v, table, lengths, window=window)
    assert out.shape == (c, h, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("pos0,c,block_q,window", [
    (0, 64, 16, 0), (13, 50, 16, 0), (40, 64, 8, 20), (8, 24, 32, 0)])
def test_paged_prefill_fetches_live_pages_once(pos0, c, block_q, window):
    """Walking the prefill grid through the kernel's clamped index map,
    each tile's distinct block indices are exactly the pages its rows
    read, and the fetches (changes of block index between consecutive
    steps) are what the engine counts as ``prefill_kv_page_reads``."""
    from repro.kernels.paged_attention import (
        prefill_page_index, prefill_page_reads)

    t, n_pages = 8, 16
    table = np.arange(100, 100 + n_pages)          # distinct physical pages
    kw = dict(chunk=c, block_q=block_q, page_tokens=t, window=window,
              n_pages=n_pages)
    fetches, prev = 0, None
    for tile in range(-(-c // block_q)):
        seen = set()
        for p in range(n_pages):
            blk = int(prefill_page_index(tile, p, table, pos0, xp=np, **kw))
            seen.add(blk)
            fetches += blk != prev
            prev = blk
        rows = range(pos0 + tile * block_q,
                     pos0 + min((tile + 1) * block_q, c))
        need = {int(table[j]) for q in rows
                for j in range(max(0, q - window + 1) // t if window else 0,
                               q // t + 1)}
        assert seen == need
    assert fetches == prefill_page_reads(pos0, c, block_q, t, window,
                                         n_pages)
    # The re-read factor: about C / block_q, against C for a kernel that
    # walks the slot's pages once per row.
    live = (pos0 + c - 1) // t - (max(0, pos0 - window + 1) // t
                                  if window else 0) + 1
    assert fetches <= live * -(-c // block_q)


def test_flash_attention_records_clamped_plan():
    """When the sequence forces the kernel below the plan's block, the
    effective plan comes back with the executed blocks and a ``+clamped``
    provenance marker instead of diverging silently."""
    from repro.core.autotile import plan_attention
    from repro.kernels.flash_attention import flash_attention

    b, h, sq, sk, d = 1, 2, 24, 24, 16
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, sk, d)), jnp.float32)
    plan = plan_attention(4096, 4096, d, dtype_bytes=4, use_tuned=False)
    assert plan.block_q > sq            # the clamp must trigger
    out, eff = flash_attention(q, k, v, plan=plan, return_plan=True)
    assert (eff.block_q, eff.block_kv) == (sq, sk)
    assert eff.source.endswith("+clamped")
    # The clamp changes bookkeeping only, never the math.
    out2 = flash_attention(q, k, v, plan=plan)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # No clamp -> the plan comes back untouched.
    small = plan_attention(sq, sk, d, dtype_bytes=4, use_tuned=False)
    _, eff2 = flash_attention(q, k, v, plan=small, return_plan=True)
    assert not eff2.source.endswith("+clamped")


def test_mlstm_chunkwise_matches_step():
    from repro.models.xlstm import mlstm_chunkwise, mlstm_step

    b, s, h, d = 1, 48, 2, 16
    keys = jax.random.split(jax.random.fold_in(KEY, 123), 5)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, h, d), jnp.float32)
    i_pre = jax.random.normal(keys[3], (b, s, h), jnp.float32)
    f_pre = jax.random.normal(keys[4], (b, s, h), jnp.float32) + 1.0

    out_c, _ = mlstm_chunkwise(q, k, v, i_pre, f_pre, chunk=16)

    import numpy as onp
    C = jnp.zeros((b, h, d, d))
    nvec = jnp.zeros((b, h, d))
    m = jnp.full((b, h), -1e30)
    outs = []
    for t in range(s):
        o, (C, nvec, m) = mlstm_step(q[:, t], k[:, t], v[:, t],
                                     i_pre[:, t], f_pre[:, t], (C, nvec, m))
        outs.append(o)
    ref = jnp.stack(outs, axis=1)
    onp.testing.assert_allclose(onp.asarray(out_c), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)

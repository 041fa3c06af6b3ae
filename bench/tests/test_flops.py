"""Operation and byte counts against hand arithmetic."""

from bench import flops, spec

dense = spec.load_family("dense")
M = dense.ModelDesc(name="t", d_model=8, n_heads=4, n_kv_heads=2,
                    head_dim=2, d_ff=16, vocab=10, n_layers=3,
                    rope_theta=1e4, norm_eps=1e-6, qkv_bias=False,
                    dtype="bfloat16", max_len=64)


def test_decode_row():
    w = dense.decode_row(M, ctx=10, dtype_bytes=2)
    # K and V of 10 tokens, 2 heads of 2, bf16; q and o of 4 heads of 2.
    assert w.attn_bytes == (2 * 10 * 2 * 2 + 2 * 4 * 2) * 2 * 3
    assert w.attn_flops == 4 * 4 * 2 * 10 * 3


def test_prefill_chunk_reads_context_once():
    w = dense.prefill_chunk(M, start=4, c=3, last=False, dtype_bytes=2)
    assert w.attn_bytes == (2 * 7 * 2 * 2 + 2 * 3 * 4 * 2) * 2 * 3
    assert w.attn_flops == 4 * 4 * 2 * (5 + 6 + 7) * 3
    assert flops.chunks(10, 4) == [(0, 4), (4, 4), (8, 2)]


def test_request_work_rows():
    pre, dec = flops.request_work(dense, M, prompt_len=10, served=5,
                                  chunk=4, dtype_bytes=2)
    assert pre.rows == 10 and dec.rows == 4

"""Operations and bytes that the served work needs, from its shapes.

Counted once for the algorithm, whatever kernel does it: a decode row
reads the K/V of its own context once; a prefill chunk reads the K/V of
its context (everything up to its last token) once, for all its rows
together.  K/V are counted at the model's head dim in the served dtype,
never at the pool's padded width and never as a re-read.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, List, Tuple


@dataclass
class Work:
    """Attention operations and bytes (all layers), and model operations."""

    attn_flops: float = 0.0
    attn_bytes: float = 0.0
    model_flops: float = 0.0
    rows: int = 0

    def __iadd__(self, o: "Work") -> "Work":
        self.attn_flops += o.attn_flops
        self.attn_bytes += o.attn_bytes
        self.model_flops += o.model_flops
        self.rows += o.rows
        return self


def _matmul_params(m) -> int:
    """Weights one token multiplies through in one layer."""
    return (m.d_model * (m.n_heads + 2 * m.n_kv_heads) * m.head_dim
            + m.n_heads * m.head_dim * m.d_model + 3 * m.d_model * m.d_ff)


def chunks(prompt_len: int, chunk: int) -> List[Tuple[int, int]]:
    """``(start, length)`` of each prefill chunk of a cold prompt."""
    return [(a, min(chunk, prompt_len - a))
            for a in range(0, prompt_len, chunk)]


def prefill_chunk(m, start: int, c: int, last: bool, dtype_bytes: int
                  ) -> Work:
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


def decode_row(m, ctx: int, dtype_bytes: int) -> Work:
    """One decode row whose query sees ``ctx`` tokens."""
    h, kv, hd, n = m.n_heads, m.n_kv_heads, m.head_dim, m.n_layers
    flops = 4.0 * h * hd * ctx * n
    byts = (2.0 * ctx * kv * hd + 2.0 * h * hd) * dtype_bytes * n
    model = 2.0 * _matmul_params(m) * n + flops + 2.0 * m.d_model * m.vocab
    return Work(flops, byts, model, 1)


def request_work(m, prompt_len: int, served: int, chunk: int,
                 dtype_bytes: int) -> Tuple[Work, Work]:
    """(prefill, decode) work of one request that was served ``served``
    tokens: its prompt's chunks, and a decode row for each token after
    the first (the first comes from the last chunk's logits)."""
    pre = Work()
    cs = chunks(prompt_len, chunk)
    for i, (a, c) in enumerate(cs):
        pre += prefill_chunk(m, a, c, i == len(cs) - 1, dtype_bytes)
    dec = Work()
    for j in range(1, served):
        dec += decode_row(m, prompt_len + j, dtype_bytes)
    return pre, dec


def call_work(m, requests: Iterable[Tuple[int, int]], chunk: int,
              dtype_bytes: int) -> Tuple[Work, Work]:
    """``request_work`` summed over ``(prompt_len, served)`` pairs."""
    pre, dec = Work(), Work()
    for p, s in requests:
        a, b = request_work(m, p, s, chunk, dtype_bytes)
        pre += a
        dec += b
    return pre, dec

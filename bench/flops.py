"""Operations and bytes that the served work needs, from its shapes.

The family module (``bench/families/<family>.py``) counts one prefill
chunk (``prefill_chunk``) and one decode row (``decode_row``); this file
cuts a request into those and sums them.  Each is counted once for the
algorithm, whatever kernel does it, as the family's module says.
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


def chunks(prompt_len: int, chunk: int) -> List[Tuple[int, int]]:
    """``(start, length)`` of each prefill chunk of a cold prompt."""
    return [(a, min(chunk, prompt_len - a))
            for a in range(0, prompt_len, chunk)]


def request_work(fam, m, prompt_len: int, served: int, chunk: int,
                 dtype_bytes: int) -> Tuple[Work, Work]:
    """(prefill, decode) work of one request that was served ``served``
    tokens, by the family module ``fam``: its prompt's chunks, and a
    decode row for each token after the first (the first comes from the
    last chunk's logits)."""
    pre = Work()
    cs = chunks(prompt_len, chunk)
    for i, (a, c) in enumerate(cs):
        pre += fam.prefill_chunk(m, a, c, i == len(cs) - 1, dtype_bytes)
    dec = Work()
    for j in range(1, served):
        dec += fam.decode_row(m, prompt_len + j, dtype_bytes)
    return pre, dec


def call_work(fam, m, requests: Iterable[Tuple[int, int]], chunk: int,
              dtype_bytes: int) -> Tuple[Work, Work]:
    """``request_work`` summed over ``(prompt_len, served)`` pairs."""
    pre, dec = Work(), Work()
    for p, s in requests:
        a, b = request_work(fam, m, p, s, chunk, dtype_bytes)
        pre += a
        dec += b
    return pre, dec

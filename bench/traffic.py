"""The one traffic generator: reads a mix file, yields requests from a seed.

A mix (``bench/traffic/<name>.json``) gives a prompt-length and an
output-length distribution (``lognormal`` with ``median``/``sigma``, or
``uniform``), each clipped to ``[min, max]``.

A batch of ``n`` requests holds ``n`` (prompt, output) length pairs:
prompt lengths at the midpoints of ``n`` equal-probability strata of
their distribution, output lengths likewise, paired by one fixed
permutation and served in that fixed order.  Every batch, in every run,
offers the same lengths in the same order; the seed draws the token ids
(and, elsewhere, the weights).  So runs with different seeds do the same
work, and only what the tokens are differs.

Prompt lengths are rounded to a granule: the largest divisor of the
planned page not above ``GRANULE_MAX``.  A prompt's last chunk is
``prompt_len % page`` tokens and each distinct remainder is a program of
its own, so the granule bounds the set-up's warm-up to ``page / granule``
partial-chunk programs.  A pair whose prompt plus output does not fit
``max_len`` has its output cut to fit.
"""

from __future__ import annotations

import json
from pathlib import Path
from statistics import NormalDist
from typing import List, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"

#: Seed of the fixed permutations that pair prompt and output strata and
#: set the serving order.
PAIRING_SEED = 20240601

#: Upper limit of the prompt-length granule, in tokens.
GRANULE_MAX = 64


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def granule(page_tokens: int) -> int:
    """The largest divisor of ``page_tokens`` that is at most
    ``GRANULE_MAX``."""
    return max(g for g in range(1, min(page_tokens, GRANULE_MAX) + 1)
               if page_tokens % g == 0)


def _strata(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the midpoints of equal-probability strata."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi)


def length_pairs(mix: dict, n: int, page_tokens: int, max_len: int,
                 scale: float = 1.0) -> List[Tuple[int, int]]:
    """The mix's ``n`` (prompt_len, output_len) pairs, in serving order.
    ``scale`` shrinks every length (the CPU rehearsal); 1.0 is the mix as
    written."""
    g = granule(page_tokens)
    prompts = _strata(mix["prompt"], n) * scale
    prompts = np.maximum(g, np.round(prompts / g) * g).astype(int)
    outs = _strata(mix["output"], n) * scale
    outs = np.maximum(1, np.round(outs)).astype(int)
    outs = outs[np.random.default_rng(PAIRING_SEED).permutation(n)]
    pairs = []
    for p, o in zip(prompts.tolist(), outs.tolist()):
        p = min(p, (max_len - 2) // g * g)
        pairs.append((p, max(1, min(o, max_len - 1 - p))))
    order = np.random.default_rng(PAIRING_SEED + 1).permutation(n)
    return [pairs[i] for i in order]


class RequestStream:
    """Batches of requests: ``next_batch()`` gives one
    ``(prompt_ids, output_len)`` for each pair, in the pairs' order, with
    token ids drawn from the seed."""

    def __init__(self, pairs: List[Tuple[int, int]], vocab: int, seed: int):
        self.pairs = pairs
        self.vocab = int(vocab)
        self.rng = np.random.default_rng(seed)

    def next_batch(self) -> List[Tuple[np.ndarray, int]]:
        return [(self.rng.integers(0, self.vocab, p, dtype=np.int32), o)
                for p, o in self.pairs]


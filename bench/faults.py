"""Faults planted in the served path underneath the harness, to show that
``correct`` comes out false when the timed path is wrong.

Each takes ``patch(obj, name, value)`` (``setattr``, or pytest's
``monkeypatch.setattr``) and replaces one function of the program:

- ``token_altered``: every sampled token is shifted by one where the
  engine samples it;
- ``state_unchanged``: the decode step hands back its K/V pool as it got
  it: no decoded token's K/V is ever written, and the paged kernel reads
  the pool without it.

A served cell has no training step, no batch mean and no exchange
between chips, so these two are the faults it can have.  Plant a fault
before the engine is built: the engine compiles what it finds then.
"""

from __future__ import annotations

from typing import Any, Callable

Patch = Callable[[Any, str, Any], None]


def token_altered(patch: Patch) -> None:
    import repro.serve.engine as eng

    real = eng.sample

    def sample(logits, cfg, key=None):
        tok = real(logits, cfg, key)
        return (tok + 1) % logits.shape[-1]

    patch(eng, "sample", sample)


def state_unchanged(patch: Patch) -> None:
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


FAULTS = {"token_altered": token_altered, "state_unchanged": state_unchanged}

"""Serve-step factory: jitted prefill + single-token decode, plan-driven.

Moved here from ``launch/trainer.py`` (which keeps a re-export): serving
is now owned by ``repro.serve``, and the step factory is where the decode
plan meets the lowered program.  Pass ``decode_plan`` (a
``HierarchicalPlan`` from ``repro.serve.plan_decode``) and the factory

  * realizes the plan's mesh-level **KV head sharding** through
    ``dist.sharding.with_kv_sharding`` (the cache's head dim is sharded
    over "model" exactly when the plan's ``kv_shard > 1``), and
  * sizes the cache buffers in whole **pages** (the plan's VMEM-leaf page
    level): ``max_len_extra`` callers are legacy; the engine passes a
    page-aligned capacity instead.

Without a plan the legacy ``cache_policy`` auto heuristics apply
unchanged (baseline dry-runs, perf_iter variants).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist.sharding import (
    ShardingRules,
    arch_rules,
    param_shardings,
    resolve_collectives,
    use_mesh_rules,
    with_batch_guard,
    with_kv_sharding,
    with_vmem_limit,
)
from repro.launch.specs import (
    batch_logical_axes,
    cache_logical_axes,
    decode_footprint,
)
from repro.models.model import Model, build_model

PyTree = Any


@dataclass
class ServeSteps:
    prefill: Callable               # (params, batch) -> (logits, cache)
    decode: Callable                # (params, cache, batch) -> (logits, cache)
    param_sharding: PyTree
    cache_sharding: PyTree
    model: Model
    plan: Any = None                # the decode HierarchicalPlan (if any)
    max_len: int = 0                # cache token capacity at prefill


@dataclass
class PagedServeSteps:
    """The paged engine's programs: one decode jit bucket for the whole
    run (pool, table and slot count are static shapes), plus chunked
    prefill -- one extra bucket per distinct chunk length, i.e. the full
    planned chunk and one per partial-final-chunk remainder."""

    decode: Callable                # (params, paged_cache, batch) -> (logits, cache)
    prefill_chunk: Callable         # (params, cache, tokens, pos0, slot) -> (logits, cache)
    param_sharding: PyTree
    cache_sharding: PyTree
    model: Model
    plan: Any = None
    encode: Optional[Callable] = None   # enc-dec: (params, enc_embeds) -> (ck, cv)


def make_serve_steps(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
    dtype=jnp.bfloat16,
    jit: bool = True,
    max_len_extra: int = 0,
    weights_tp_only: bool = False,
    cache_head_sharded: bool = False,
    cache_seq_sharded: bool = False,
    cache_policy: str = "auto",
    collectives: str = "gspmd",
    plan: Optional[Any] = None,
    decode_plan: Optional[Any] = None,
) -> ServeSteps:
    """Serve-step factory.

    ``cache_policy="auto"`` applies the §Perf-winning placement: shard the
    KV cache over heads when kv_heads divides the model axis (attention
    stays shard-local, zero cache collectives, cell 3: -93% bound), else
    over the sequence dim with grouped-GQA decode (cell 2: -80% bound);
    explicit ``cache_head_sharded`` / ``cache_seq_sharded`` flags override
    (used by the baseline dry-run via ``cache_policy="baseline"`` and by
    perf_iter).  ``decode_plan`` overrides all of it with the hierarchical
    planner's decode-workload choice (see module docstring).
    """
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    heads_divide = cfg.n_kv_heads % model_size == 0
    # The sharded buffer is the padded cache (seq_len + extra) -- pjit
    # in/out shardings require exact divisibility.
    seq_divides = (shape.seq_len + max_len_extra) % model_size == 0
    kv_shard = 0
    if decode_plan is not None:
        kv_shard = decode_plan.kv_shard()
        cache_head_sharded = kv_shard > 1 and heads_divide
        cache_seq_sharded = False
        cache_policy = "plan"
    if cache_policy == "auto" and not (cache_head_sharded or cache_seq_sharded):
        if not heads_divide and seq_divides and shape.kind == "decode":
            cache_seq_sharded = True
        elif heads_divide:
            cache_head_sharded = True
    long_context = shape.seq_len >= 262144 or cache_seq_sharded
    if cache_head_sharded and heads_divide:
        # Head sharding: attention local per head shard, no distributed
        # softmax; preferred whenever the head count divides the axis.
        long_context = False
    if rules is None:
        # Serving memory model: bf16 weights only (no master copy /
        # moments), and the KV cache as the reserved term -- it shards over
        # both the batch (data) and head (model) axes, so the global
        # footprint divides by the full mesh.
        rules = arch_rules(
            cfg, mesh, seq_sharded=long_context,
            state_bytes_per_param=2,
            act_bytes=decode_footprint(
                cfg, shape, shape.seq_len + max_len_extra) // mesh.size,
            plan=plan)
    rules = with_batch_guard(rules, mesh, shape.global_batch)
    rules = resolve_collectives(rules, collectives)
    if decode_plan is not None:
        rules = with_kv_sharding(rules, kv_shard if cache_head_sharded else 1)
    if weights_tp_only:
        # Perf variant: serving replicates weights across the data axes
        # (memory permitting) so no per-step FSDP all-gather is emitted.
        pr = dict(rules.param_rules)
        pr["embed"] = None
        rules = ShardingRules(pr, dict(rules.act_rules), meta=dict(rules.meta))
    model = build_model(cfg, remat="none")
    specs = model.param_specs()
    p_shard = param_shardings(mesh, rules, specs)
    max_len = shape.seq_len + max_len_extra

    cache_tpl = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, max_len, dtype,
                                 enc_len=shape.seq_len))
    c_axes = cache_logical_axes(cfg, cache_tpl, long_context)
    c_shard = jax.tree.map(
        lambda ax: NamedSharding(mesh, rules.act_spec(ax)),
        c_axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )
    d_axes = batch_logical_axes(cfg, "decode")
    d_shard = {k: NamedSharding(mesh, rules.act_spec(v))
               for k, v in d_axes.items()}
    t_axes = batch_logical_axes(cfg, "train")
    t_shard = {k: NamedSharding(mesh, rules.act_spec(v))
               for k, v in t_axes.items() if k != "labels"}

    def prefill_fn(params, batch):
        with use_mesh_rules(mesh, rules):
            return model.prefill(params, batch, max_len, dtype=dtype)

    def decode_fn(params, cache, batch):
        with use_mesh_rules(mesh, rules):
            return model.decode_step(params, cache, batch, dtype=dtype)

    if jit:
        prefill_fn = jax.jit(
            prefill_fn,
            in_shardings=(p_shard, t_shard),
            out_shardings=(None, c_shard),
        )
        decode_fn = jax.jit(
            decode_fn,
            in_shardings=(p_shard, c_shard, d_shard),
            out_shardings=(None, c_shard),
            donate_argnums=(1,),
        )
    return ServeSteps(prefill=prefill_fn, decode=decode_fn,
                      param_sharding=p_shard, cache_sharding=c_shard,
                      model=model, plan=decode_plan, max_len=max_len)


def make_paged_steps(
    cfg: ModelConfig,
    mesh: Mesh,
    cache_tpl: PyTree,
    n_slots: int,
    max_len: int,
    dtype=jnp.bfloat16,
    jit: bool = True,
    decode_plan: Optional[Any] = None,
    collectives: str = "gspmd",
) -> PagedServeSteps:
    """Lower the paged decode step (``Model.decode_step_paged``).

    ``cache_tpl`` is the pooled cache pytree from
    ``serve.pages.init_paged_cache`` (shapes only are read).  The plan's
    KV head sharding applies to the pool exactly as it does to the dense
    cache -- ``with_kv_sharding`` maps the pool's "kv_heads" axis and pins
    the page dim ("kv_pages") unsharded, since a page is the VMEM
    streaming granule of ONE chip.  Unlike the cohort factory there is
    exactly one jit bucket: pool, table and slot count are static.
    """
    from repro.serve.pages import paged_cache_logical_axes

    model_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    heads_divide = cfg.n_kv_heads % model_size == 0
    kv_shard = decode_plan.kv_shard() if decode_plan is not None else 1
    shape = ShapeConfig("paged", 1, n_slots, "decode")
    rules = arch_rules(
        cfg, mesh, state_bytes_per_param=2,
        act_bytes=decode_footprint(cfg, shape, max_len) // mesh.size)
    rules = with_batch_guard(rules, mesh, n_slots)
    rules = resolve_collectives(rules, collectives)
    rules = with_kv_sharding(rules, kv_shard if heads_divide else 1)
    page = decode_plan.page_plan() if decode_plan is not None else None
    if page and page.get("vmem_limit_bytes"):
        rules = with_vmem_limit(rules, page["vmem_limit_bytes"])
    model = build_model(cfg, remat="none")
    p_shard = param_shardings(mesh, rules, model.param_specs())

    c_axes = paged_cache_logical_axes(cfg, cache_tpl)
    c_shard = jax.tree.map(
        lambda ax: NamedSharding(mesh, rules.act_spec(ax)),
        c_axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )
    d_axes = batch_logical_axes(cfg, "decode")
    d_shard = {k: NamedSharding(mesh, rules.act_spec(v))
               for k, v in d_axes.items()}

    def decode_fn(params, cache, batch):
        with use_mesh_rules(mesh, rules):
            return model.decode_step_paged(params, cache, batch, dtype=dtype)

    def prefill_chunk_fn(params, cache, tokens, pos0, slot):
        with use_mesh_rules(mesh, rules):
            return model.prefill_chunk(
                params, cache,
                {"tokens": tokens, "pos0": pos0, "slot": slot}, dtype=dtype)

    encode_fn = None
    if cfg.family == "enc_dec":
        def encode_fn(params, enc_embeds):
            with use_mesh_rules(mesh, rules):
                return model.encode_cross(
                    params, {"enc_embeds": enc_embeds}, dtype=dtype)

    if jit:
        from jax.sharding import PartitionSpec

        repl = NamedSharding(mesh, PartitionSpec())
        decode_fn = jax.jit(
            decode_fn,
            in_shardings=(p_shard, c_shard, d_shard),
            out_shardings=(None, c_shard),
            donate_argnums=(1,),
        )
        # One retrace per distinct chunk length: the engine cuts prompts
        # into planned-page-sized chunks, so the buckets are {page, each
        # distinct prompt_len % page} -- bounded, and the full-chunk
        # bucket dominates.
        prefill_chunk_fn = jax.jit(
            prefill_chunk_fn,
            in_shardings=(p_shard, c_shard, repl, repl, repl),
            out_shardings=(None, c_shard),
            donate_argnums=(1,),
        )
        if encode_fn is not None:
            encode_fn = jax.jit(encode_fn, in_shardings=(p_shard, repl))
    return PagedServeSteps(decode=decode_fn, prefill_chunk=prefill_chunk_fn,
                           param_sharding=p_shard, cache_sharding=c_shard,
                           model=model, plan=decode_plan, encode=encode_fn)

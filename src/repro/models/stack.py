"""Generic layer-stack machinery: build stacked ParamDefs for a repeating
group of heterogeneous sublayers, and apply them under scan with the
FCDP gather + remat schedule.

A "plan" is a list of positions; each position is a tuple of sublayer
kinds. The whole group repeats `n_groups` times (params stacked on a
leading 'stack' dim, applied with jax.lax.scan).

This module owns the model-specific part only -- building per-position
sublayer bodies and dispatching them. WHICH gather runs when is the
streaming gather scheduler's job (``core/schedule.py``):
``apply_stack`` hands its group body to a :class:`GatherScheduler`,
which runs either the sequential schedule (each scan step fuses its own
two-stage gather; ``SystemConfig.prefetch_depth == 0``) or the depth-k
prefetch schedule (a ring buffer of k in-flight stage-1 / DCN gather
caches riding the scan carry, so layer i+k's DCN transfer overlaps
layer i's compute and the backward reads the carried caches back
instead of re-gathering). Both the stateless scan (training loss /
encoder) and the stateful prefill/decode scan run under the scheduler;
strategy gating and the memory trade are documented in
``core/schedule.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SystemConfig
from repro.core.fcdp import checkpoint_layer
from repro.core.partition import ParamDef, tree_map_defs
from repro.core.schedule import GatherScheduler
from repro.core.strategy import GatherPlan, resolve_strategy
from repro.models import sublayers as sl
from repro.models.common import MeshInfo

_is_plan = lambda x: isinstance(x, GatherPlan)  # noqa: E731

KIND_DEFS = {
    "attn": sl.attn_defs,
    "xattn": sl.xattn_defs,
    "mlp": sl.mlp_defs,
    "moe": sl.moe_defs,
    "mamba": sl.mamba_defs,
    "rwkv_tm": sl.rwkv_tm_defs,
    "rwkv_cm": sl.rwkv_cm_defs,
}

STATEFUL_KINDS = ("attn", "xattn", "mamba", "rwkv_tm", "rwkv_cm")

# the program scope of each sublayer kind's ops; other kinds by their name
SCOPES = {"attn": "attention"}


def group_defs(cfg: ModelConfig, plan: List[Tuple[str, ...]], tp: int,
               sys: Optional[SystemConfig] = None
               ) -> Dict[str, Dict[str, Dict[str, ParamDef]]]:
    """Unstacked defs for one group: {pos{i}: {kind: {param: def}}}."""
    out: Dict[str, Any] = {}
    for i, kinds in enumerate(plan):
        pos: Dict[str, Any] = {}
        for kind in kinds:
            if kind == "moe":
                pos[kind] = sl.moe_defs(
                    cfg, tp, weight_resident=bool(
                        sys and sys.moe_weight_resident))
            else:
                pos[kind] = KIND_DEFS[kind](cfg, tp)
        out[f"pos{i}"] = pos
    return out


def stack_defs(defs, n_groups: int):
    """Prepend the scan ('stack') dimension to every def."""
    def add_stack(d: ParamDef) -> ParamDef:
        return dataclasses.replace(
            d, shape=(n_groups,) + d.shape, dims=("stack",) + d.dims)
    return tree_map_defs(add_stack, defs)


def apply_sublayer(kind: str, cfg, sys, mi, p, x, ctx: Dict[str, Any],
                   state=None):
    """Dispatch one sublayer under its program scope (``SCOPES``).
    Returns (x, new_state, aux)."""
    with jax.named_scope(SCOPES.get(kind, kind)):
        return _dispatch(kind, cfg, sys, mi, p, x, ctx, state)


def _dispatch(kind: str, cfg, sys, mi, p, x, ctx: Dict[str, Any], state):
    if kind == "attn":
        if ctx.get("paged"):
            x, new_state = sl.attn_paged(
                cfg, sys, mi, p, x, state, ctx["positions"],
                ctx["page_table"],
                prefill=bool(ctx.get("prefill_chunk")))
            return x, new_state, 0.0
        if ctx.get("decode"):
            x, new_state = sl.attn_decode(
                cfg, sys, mi, p, x, state,
                seq_sharded=ctx.get("seq_sharded", False))
            return x, new_state, 0.0
        x, new_cache = sl.attn_apply(
            cfg, sys, mi, p, x, ctx["positions"],
            causal=ctx.get("causal", True),
            kv_cache=(state["k"], state["v"], state["idx"])
            if (state is not None and ctx.get("prefill")) else None)
        if new_cache is not None:
            k, v, idx = new_cache
            return x, {"k": k, "v": v, "idx": idx}, 0.0
        return x, state, 0.0
    if kind == "xattn":
        if ctx.get("prefill") and state is not None:
            # project encoder output once; store for decode
            k, v = sl.xattn_make_kv(cfg, mi, p, ctx["enc_out"])
            state = {"k": k.astype(state["k"].dtype),
                     "v": v.astype(state["v"].dtype)}
            x, _ = sl.xattn_apply(cfg, sys, mi, p, x, (k, v))
            return x, state, 0.0
        if ctx.get("decode"):
            x, _ = sl.xattn_apply(cfg, sys, mi, p, x,
                                  (state["k"], state["v"]))
            return x, state, 0.0
        k, v = sl.xattn_make_kv(cfg, mi, p, ctx["enc_out"])
        x, _ = sl.xattn_apply(cfg, sys, mi, p, x, (k, v))
        return x, state, 0.0
    if kind == "mlp":
        return sl.mlp_apply(cfg, sys, mi, p, x), state, 0.0
    if kind == "moe":
        x, aux = sl.moe_apply(cfg, sys, mi, p, x,
                              sharded=bool(ctx.get("moe_sharded")))
        return x, state, aux
    if kind == "mamba":
        if ctx.get("decode"):
            x, new_state = sl.mamba_decode(cfg, sys, mi, p, x, state)
            return x, new_state, 0.0
        if ctx.get("prefill") and state is not None:
            x, new_state = sl.mamba_prefill(cfg, sys, mi, p, x)
            return x, new_state, 0.0
        return sl.mamba_apply(cfg, sys, mi, p, x), state, 0.0
    if kind == "rwkv_tm":
        if ctx.get("decode"):
            x, new_state = sl.rwkv_tm_decode(cfg, sys, mi, p, x, state)
            return x, new_state, 0.0
        if ctx.get("prefill") and state is not None:
            x, new_state = sl.rwkv_tm_prefill(cfg, sys, mi, p, x)
            return x, new_state, 0.0
        return sl.rwkv_tm_apply(cfg, sys, mi, p, x), state, 0.0
    if kind == "rwkv_cm":
        if ctx.get("decode"):
            x, new_state = sl.rwkv_cm_decode(cfg, sys, mi, p, x, state)
            return x, new_state, 0.0
        if ctx.get("prefill") and state is not None:
            x, new_state = sl.rwkv_cm_prefill(cfg, sys, mi, p, x)
            return x, new_state, 0.0
        return sl.rwkv_cm_apply(cfg, sys, mi, p, x), state, 0.0
    raise ValueError(f"unknown sublayer kind {kind!r}")


def init_group_state(cfg, plan, mi: MeshInfo, batch_local: int,
                     max_len: int, n_groups: int,
                     seq_sharded: bool = False, enc_len: int = 0):
    """Decode state for one group, stacked over n_groups."""
    out: Dict[str, Any] = {}
    for i, kinds in enumerate(plan):
        pos: Dict[str, Any] = {}
        for kind in kinds:
            if kind == "attn":
                pos[kind] = sl.attn_init_state(cfg, mi, batch_local, max_len,
                                               seq_sharded)
            elif kind == "xattn":
                pos[kind] = sl.xattn_init_state(cfg, mi, batch_local, enc_len)
            elif kind == "mamba":
                pos[kind] = sl.mamba_init_state(cfg, mi, batch_local)
            elif kind == "rwkv_tm":
                pos[kind] = sl.rwkv_tm_init_state(cfg, mi, batch_local)
            elif kind == "rwkv_cm":
                pos[kind] = sl.rwkv_cm_init_state(cfg, mi, batch_local)
        if pos:
            out[f"pos{i}"] = pos
    # stack over groups
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape), out)


def init_paged_group_state(cfg, plan, mi: MeshInfo, n_pages: int,
                           page_size: int, n_groups: int):
    """Paged decode state for one group, stacked over n_groups. The
    paged serve path shares one page table across all layers, so the
    only per-layer state is the attention KV pool itself; any other
    stateful mixer in the plan has no paged equivalent."""
    out: Dict[str, Any] = {}
    for i, kinds in enumerate(plan):
        pos: Dict[str, Any] = {}
        for kind in kinds:
            if kind == "attn":
                pos[kind] = sl.attn_init_paged_state(cfg, mi, n_pages,
                                                     page_size)
            elif kind in STATEFUL_KINDS:
                raise ValueError(
                    "paged serving supports attention-only stacks; "
                    f"plan position {i} has stateful kind {kind!r}")
        if pos:
            out[f"pos{i}"] = pos
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape), out)


def apply_stack(cfg: ModelConfig, sys: SystemConfig, mi: MeshInfo,
                plan: List[Tuple[str, ...]],
                stacked_params, stacked_plans, x, ctx: Dict[str, Any],
                stacked_state=None, placement: Optional[str] = None,
                strategy=None):
    """Scan the group over the stack dimension under the streaming
    gather scheduler (core/schedule.py: sequential or depth-k prefetch).

    stacked_params: pytree with leading stack dim on every leaf.
    stacked_plans: GatherPlan tree (body-level dims, see plan_tree(stacked=True)).
    strategy: resolved ShardingStrategy or CompositeStrategy (required:
      the per-leaf resolution happens at model construction; this module
      never resolves SystemConfig.mode itself).
    Returns (x, new_stacked_state, aux_sum).
    """
    strategy = resolve_strategy(strategy)

    moe_sharded = (getattr(sys, "moe_serve_sharded", False)
                   and ctx.get("decode"))
    if moe_sharded:
        ctx = dict(ctx, moe_sharded=True)

    def make_group_body(gather_leaf):
        """Group apply; ``gather_leaf`` reconstructs one param leaf --
        the full two-stage gather on the sequential schedule, stage 2
        only when consuming the prefetched stage-1 cache."""
        def group_body(x, params_slice, state_slice):
            new_state: Dict[str, Any] = {}
            aux = jnp.float32(0)
            for i, kinds in enumerate(plan):
                key = f"pos{i}"
                pos_new = {}
                for kind in kinds:
                    p_shard = params_slice[key][kind]
                    gplan = stacked_plans[key][kind]
                    if kind == "moe" and moe_sharded:
                        # gather-free expert weights: raw shards + plans
                        p = {k: (gather_leaf(v, gplan[k])
                                 if not k.startswith("we_") else v)
                             for k, v in p_shard.items()}
                        p["_we_plans"] = {k: gplan[k] for k in p_shard
                                          if k.startswith("we_")}
                    else:
                        p = jax.tree.map(gather_leaf, p_shard, gplan,
                                         is_leaf=_is_plan)
                    st = (state_slice.get(key, {}).get(kind)
                          if state_slice else None)
                    x, st_new, a = apply_sublayer(kind, cfg, sys, mi, p, x,
                                                  ctx, st)
                    aux = aux + a
                    if st_new is not None and kind in STATEFUL_KINDS:
                        pos_new[kind] = st_new
                if pos_new:
                    new_state[key] = pos_new
            return x, new_state, aux
        return group_body

    def wrap(body):
        return checkpoint_layer(body, strategy, sys.activation_policy,
                                sys.host_offload, placement=placement)

    from repro.models.common import pvary_like
    aux0 = pvary_like(jnp.float32(0), x)
    # the gather-free sharded-MoE decode path consumes raw expert shards;
    # pre-gathering them would break its partial-contraction math
    sched = GatherScheduler(strategy, sys, mi, stacked_plans,
                            enabled=not moe_sharded)
    # ops under ``stack`` and no inner scope are the scan's own slicing
    # and stashing of the stacked buffers
    with jax.named_scope("stack"):
        return sched.run(make_group_body, wrap, stacked_params, x, aux0,
                         stacked_state)

"""Parameter partitioning: ParamDef trees and their storage layouts.

Every parameter is described by a ParamDef whose `dims` tag each array
dimension with a logical role:

  'stack' - scan-group dimension (never sharded)
  'fsdp'  - ZeRO-3 sharding dimension (gathered per layer inside the step)
  'tp'    - tensor/expert-parallel dimension (owned shard, never gathered)
  None    - unsharded

WHICH mesh axes the fsdp dim shards over is a per-tensor decision owned
by ``repro.core.strategy`` (full ('data','pod') sharding for the
zero3-family strategies -- intra-major, so the stage-1-then-stage-2
gather reconstructs true global order -- pod-replicated ('data',) for
MiCS and for frozen FCDP-Comm params), resolved per ParamDef via
``strategy.resolve_strategies`` (explicit ``ParamDef.strategy`` tag >
``SystemConfig.mode_overrides`` rule > ``mode``). The module-level
helpers here accept a mode name or a resolved ``ShardingStrategy`` and
delegate; on the single-pod mesh ('data','model') there is no pod axis
and the fsdp axes collapse to ('data',).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.strategy import resolve_strategy


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    dims: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"          # normal | zeros | ones | embed
    init_scale: float = 1.0
    frozen: bool = False          # FCDP-Comm classification (set by peft)
    label: str = ""               # dotted path, filled by label_tree
    # 'inter_only': ZeRO-shard only over the slow (pod) axis, keeping the
    # tensor resident within the pod -- the weight-stationary trade for
    # tensors whose per-step gather volume exceeds their resident size
    # (MoE expert weights; beyond-paper, see EXPERIMENTS.md SSPerf)
    fsdp_scope: str = "full"      # full | inter_only
    # per-tensor sharding strategy. None resolves through
    # SystemConfig.mode_overrides / SystemConfig.mode at
    # StepBundle/model construction (core.strategy.resolve_strategies);
    # an explicit name here wins over both. After resolution every leaf
    # carries its resolved name, which is the dispatch/accounting key
    # for the CompositeStrategy facade and the per-group planner split.
    strategy: Optional[str] = None
    # the leaf is consumed as the RHS of one [..., K] @ [K, N] output
    # projection routed through models/layers.matmul -- the consumption
    # pattern the gather-fused collective matmul requires. Opt-in at the
    # def site because shape alone cannot tell a projection from, e.g.,
    # an embedding table with the same ("tp","fsdp") dims; the plan-level
    # rule in core/strategy.gather_plan gates further.
    fusable: bool = False

    def __post_init__(self):
        assert len(self.shape) == len(self.dims), (self.shape, self.dims)

    @property
    def fsdp_dim(self) -> Optional[int]:
        return self.dims.index("fsdp") if "fsdp" in self.dims else None

    @property
    def tp_dim(self) -> Optional[int]:
        return self.dims.index("tp") if "tp" in self.dims else None

    def size(self) -> int:
        return math.prod(self.shape)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_map_defs(fn: Callable, tree, *rest):
    return jax.tree.map(fn, tree, *rest, is_leaf=is_def)


def label_tree(tree):
    """Attach dotted-path labels to every ParamDef in the tree."""
    paths_vals, treedef = jax.tree.flatten_with_path(tree, is_leaf=is_def)
    out = []
    for path, pdef in paths_vals:
        name = ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        out.append(replace(pdef, label=name))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Storage layout
# ---------------------------------------------------------------------------

def storage_fsdp_axes(mesh, mode, frozen: bool) -> Tuple[str, ...]:
    """Which mesh axes the fsdp dim is sharded over in storage.

    ``mode`` is a strategy name or ShardingStrategy; the layout decision
    (and the FCDP-Comm frozen asymmetry) lives on the strategy object.
    """
    return resolve_strategy(mode).storage_fsdp_axes(mesh, frozen)


def effective_fsdp_axes(pdef: "ParamDef", mesh, mode) -> Tuple[str, ...]:
    return resolve_strategy(mode).effective_fsdp_axes(pdef, mesh)


def storage_spec(pdef: ParamDef, mesh, mode, min_shard_size: int = 0) -> P:
    return resolve_strategy(mode).storage_spec(pdef, mesh, min_shard_size)


def spec_tree(defs, mesh, mode: str, min_shard_size: int = 0):
    return tree_map_defs(
        lambda d: storage_spec(d, mesh, mode, min_shard_size), defs)


def sharding_tree(defs, mesh, mode: str, min_shard_size: int = 0):
    return tree_map_defs(
        lambda d: NamedSharding(mesh, storage_spec(d, mesh, mode, min_shard_size)),
        defs)


def shape_dtype_tree(defs, mesh, mode: str, min_shard_size: int = 0):
    """ShapeDtypeStruct tree for dry-run lowering (no allocation)."""
    return tree_map_defs(
        lambda d: jax.ShapeDtypeStruct(
            d.shape, d.dtype,
            sharding=NamedSharding(mesh, storage_spec(d, mesh, mode, min_shard_size))),
        defs)


# ---------------------------------------------------------------------------
# Initialization (smoke tests / examples only; dry-run never allocates)
# ---------------------------------------------------------------------------

def _init_one(key, pdef: ParamDef):
    if pdef.init == "zeros":
        return jnp.zeros(pdef.shape, pdef.dtype)
    if pdef.init == "ones":
        return jnp.ones(pdef.shape, pdef.dtype)
    fan_in = pdef.shape[-2] if len(pdef.shape) >= 2 else pdef.shape[-1]
    scale = pdef.init_scale / math.sqrt(max(fan_in, 1))
    if pdef.init == "embed":
        scale = pdef.init_scale * 0.02
    return (jax.random.normal(key, pdef.shape, jnp.float32) * scale).astype(pdef.dtype)


def init_params(defs, seed: int = 0, mesh=None, mode=None,
                min_shard_size: int = 0):
    """Materialize parameters; with a mesh, place them in storage layout."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(jax.random.key(seed), max(len(leaves), 1))
    vals = []
    for k, d in zip(keys, leaves):
        v = _init_one(k, d)
        if mesh is not None:
            v = jax.device_put(
                v, NamedSharding(mesh, storage_spec(d, mesh, mode, min_shard_size)))
        vals.append(v)
    return jax.tree.unflatten(treedef, vals)


def count_tree_params(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_def)
    return sum(d.size() for d in leaves)


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m

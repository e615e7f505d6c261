"""FCDP-Sched: the two-stage parameter gather and its caching schedule.

The paper's per-layer schedule (Fig. 4) maps onto JAX as:

  stage 1 (inter / DCN):  w_cache = all_gather(w_shard, 'pod')
  stage 2 (intra / ICI):  w_full  = all_gather(w_cache, 'data')

The layer consuming ``w_full`` is wrapped in ``jax.checkpoint`` whose
policy assigns the named value ``fcdp_cache`` per the strategy's
``cache_placement`` (see repro.core.strategy):

  zero3   -> Recompute   : backward re-runs stage 1 + stage 2 (2x inter AG)
  zeropp  -> Saveable    : cached shard lives in HBM, backward re-runs stage 2
  fcdp    -> Offloadable : cached shard lives in pinned host memory,
                           backward re-runs stage 2 only  (the paper)
  mics    -> storage is already pod-replicated; stage 1 is empty and the
             single intra stage recomputes (fwd+bwd intra AG, no DCN AG)

On a mesh without a 'pod' axis (single pod) there is no slow tier; the
cache boundary moves to after stage 2 (cache the fully gathered weight)
so zeropp/fcdp still eliminate the backward all-gather, reproducing the
paper's N=1 limit.

Frozen parameters (FCDP-Comm) are *stored* in the cached layout
(pod-replicated, intra-sharded, host-resident): their reconstruction
never touches DCN and they receive no gradient. See core/comm.py.

The gather is exposed both fused (``gather_param``) and split into its
two stages (``gather_stage1`` / ``gather_stage2``) so the streaming
gather scheduler (core/schedule.py) can issue layer i+k's stage-1 DCN
gather concurrently with layer i's compute, and ``_ag_fn`` (the
frozen/trainable gather-primitive selector) is shared with the
scheduler's leaf-level stage-1 helpers.
"""
from __future__ import annotations

from typing import Optional

import jax
# the remat policy matches the primitive checkpoint_name binds, which
# JAX does not export; no fallback: a policy that saved nothing would
# quietly turn fcdp into a full-remat zero3
from jax._src.ad_checkpoint import name_p
from jax.ad_checkpoint import (Offloadable, Recompute, Saveable,
                               checkpoint_name)

from repro.compat import all_gather_invariant
from repro.core.partition import ParamDef
from repro.core.residency import residency_of
from repro.core.strategy import GatherPlan, resolve_strategy

CACHE_NAME = "fcdp_cache"
FULL_NAME = "fcdp_full"
ACT_NAME = "act_ckpt"


def cache_name(plan: GatherPlan) -> str:
    """Placement-suffixed checkpoint name of one plan's cache boundary.

    The placement travels in the name (``fcdp_cache:host`` etc.) so ONE
    remat policy can serve a layer body whose leaves belong to different
    strategy groups (per-tensor mixed sharding): an fcdp-group weight
    offloads its stage-1 cache to pinned host while a mics-group expert
    in the same body recomputes its gather, without the policy knowing
    which strategy produced which mark."""
    return f"{CACHE_NAME}:{residency_of(plan).cache}"


def make_gather_plan(pdef: ParamDef, mesh, mode,
                     min_shard_size: int = 0,
                     compress_bwd: bool = False,
                     param_compress: bool = False,
                     quant_impl: str = "jnp",
                     fused_matmul: str = "none",
                     fused_impl: str = "jnp") -> GatherPlan:
    """Derive the gather plan matching ``storage_spec`` for this param.
    ``mode`` is a strategy name or ShardingStrategy object."""
    return resolve_strategy(mode).gather_plan(
        pdef, mesh, min_shard_size, compress_bwd, param_compress, quant_impl,
        fused_matmul, fused_impl)


def plan_tree(defs, mesh, mode, min_shard_size: int = 0,
              compress_bwd: bool = False, param_compress: bool = False,
              quant_impl: str = "jnp", fused_matmul: str = "none",
              fused_impl: str = "jnp"):
    return resolve_strategy(mode).plan_tree(
        defs, mesh, min_shard_size, compress_bwd, param_compress, quant_impl,
        fused_matmul, fused_impl)


@jax.tree_util.register_pytree_node_class
class FusedParam:
    """A stage-1 cached shard standing in for the fully gathered weight.

    When a plan is flagged ``fused``, ``gather_stage2`` skips the intra
    all-gather and hands the consumer this wrapper instead: the cache
    (marked for the remat policy exactly like the unfused path) plus the
    plan, which carries the ring axis and mode. ``models/layers.matmul``
    dispatches on it -- the stage-2 gather then happens INSIDE the
    consuming matmul's ring schedule (kernels/collective_matmul.py),
    overlapped chunk by chunk. Registered as a pytree so it rides
    ``jax.tree`` maps, scan carries, and ``jax.checkpoint`` untouched;
    the plan is static aux data."""

    def __init__(self, cache: jax.Array, plan: GatherPlan):
        self.cache = cache
        self.plan = plan

    def tree_flatten(self):
        return (self.cache,), self.plan

    @classmethod
    def tree_unflatten(cls, plan, children):
        return cls(children[0], plan)

    def __repr__(self) -> str:
        return f"FusedParam({getattr(self.cache, 'shape', None)}, " \
               f"fused={self.plan.fused!r})"


def _ag_fn(plan: GatherPlan):
    """Gather primitive for this plan.

    Frozen params (FCDP-Comm / serving) gather with the *invariant*
    all-gather: they receive no gradient, and the invariant type keeps
    downstream values replicated over the gathered axes (required for
    serve-step output typing). Trainable params use the varying
    all-gather, whose transpose is the ZeRO-3 gradient reduce-scatter.
    """
    if residency_of(plan).invariant_gather:
        def ag(x, axes, axis):
            for a in axes:  # invariant AG takes one axis at a time
                x = all_gather_invariant(x, a, axis=axis, tiled=True)
            return x
    else:
        def ag(x, axes, axis):
            return jax.lax.all_gather(x, axes, axis=axis, tiled=True)
    return ag


def gather_stage1(w: jax.Array, plan: GatherPlan) -> jax.Array:
    """Stage 1 (inter / DCN) all-gather only: shard -> cached shard.

    Identity when the plan has no inter axes (single pod, MiCS,
    FCDP-Comm frozen layout). Must run inside shard_map. Its ops carry
    the program scope ``fcdp.gather1``."""
    if not plan.is_gathered or not plan.inter_axes:
        return w
    with jax.named_scope("fcdp.gather1"):
        return _stage1(w, plan)


def _stage1(w: jax.Array, plan: GatherPlan) -> jax.Array:
    # the residency layer guarantees a non-trainable leaf never carries a
    # quantized transport (ParamResidency enforces it at construction),
    # so the compression branches need no local frozen re-derivation
    res = residency_of(plan)
    if res.quantized_gather and len(plan.inter_axes) == 1:
        # qwZ: int8 blocks + fp32 scales on the DCN wire, dequantized on
        # arrival -- what lands in the (host) cache is the dequantized
        # bf16 stage-1 view, so backward reuse stays free/full-precision
        from repro.core.grad_compress import quantized_stage1_gather
        return quantized_stage1_gather(w, plan.inter_axes[0], plan.fsdp_dim,
                                       res.quantized_reduce, plan.quant_impl)
    if res.quantized_reduce and len(plan.inter_axes) == 1:
        from repro.core.grad_compress import compressed_stage1_gather
        return compressed_stage1_gather(w, plan.inter_axes[0], plan.fsdp_dim,
                                        plan.quant_impl)
    return _ag_fn(plan)(w, plan.inter_axes, plan.fsdp_dim)


def gather_stage2(w: jax.Array, plan: GatherPlan) -> jax.Array:
    """Stage 2 (intra / ICI) all-gather: cached shard -> full (TP-local)
    parameter, with the cache/full named-checkpoint boundaries marked for
    the remat policy. Must run inside shard_map.

    Fused plans return a :class:`FusedParam` instead of gathering: the
    cache boundary is marked identically (so the remat placement is
    unchanged) but the intra gather -- and with it the FULL_NAME mark,
    since no full weight ever materializes -- is deferred into the
    consuming matmul's ring. Its ops carry the program scope
    ``fcdp.gather2``."""
    if not plan.is_gathered:
        return w
    with jax.named_scope("fcdp.gather2"):
        return _stage2(w, plan)


def _stage2(w: jax.Array, plan: GatherPlan):
    if plan.cache_after == 1:
        w = checkpoint_name(w, cache_name(plan))
    if plan.is_fused and plan.intra_axes:
        return FusedParam(w, plan)
    if plan.intra_axes:
        w = _ag_fn(plan)(w, plan.intra_axes, plan.fsdp_dim)
    if plan.cache_after == 2:
        w = checkpoint_name(w, cache_name(plan))
    return checkpoint_name(w, FULL_NAME)


def gather_param(w: jax.Array, plan: GatherPlan) -> jax.Array:
    """Reconstruct the full (TP-local) parameter from its ZeRO shard
    (both stages fused -- the sequential, non-prefetched schedule)."""
    if not plan.is_gathered:
        return w
    return gather_stage2(gather_stage1(w, plan), plan)


# ---------------------------------------------------------------------------
# Remat policies (FCDP-Sched placement decisions)
# ---------------------------------------------------------------------------

def make_remat_policy(cache_placement: str, activation_policy: str = "save_all",
                      host_offload: bool = True,
                      promote_to_device: bool = False):
    """Build a jax.checkpoint policy.

    cache_placement: 'device' | 'host' | 'regather' -- the fallback for
        legacy unsuffixed cache marks; plans emitted by the strategies
        carry their own placement in the mark name (``fcdp_cache:host``)
        so a mixed-strategy layer body needs only this one policy.
    activation_policy: 'save_all' (paper-faithful, torch-like) |
                       'block_io' (full activation remat) |
                       'offload_acts' (named activations offloaded)
    promote_to_device: FCDP-Cache's tau split (leading layer segments
        keep the cached shard in HBM): promotes HOST-placed caches to
        device and leaves regather/device groups untouched, so the
        per-segment promotion is safe on mixed-strategy bodies.
    """
    # torch-autograd-like 'save_all': keep the outputs of matmuls and of
    # paid-for collectives; recompute cheap elementwise chains (incl. the
    # f32 norm upcasts, which would otherwise dominate activation memory).
    SAVE_PRIMS = {"dot_general", "conv_general_dilated", "psum", "psum2",
                  "psum_invariant", "all_to_all", "psum_scatter"}

    # 'save_collectives' (beyond-paper perf policy, see EXPERIMENTS.md
    # SSPerf): save only paid-for collective outputs so the backward remat
    # recomputes matmuls (cheap, local) but never re-runs a psum /
    # all_to_all (expensive, global). ~-33% on the TP-activation
    # all-reduce volume vs block_io at ~0.25 GiB/layer extra HBM.
    COLLECTIVE_SAVE_PRIMS = {"psum", "psum2", "psum_invariant",
                             "all_to_all", "psum_scatter"}

    def policy(prim, *avals, **params):
        s = getattr(prim, "name", str(prim))
        if s == "all_gather" or s == "all_gather_invariant":
            # gathered tensors are never implicitly saved: the whole point
            return Recompute
        if prim is name_p:
            name = params.get("name")
            if name == CACHE_NAME or (name or "").startswith(CACHE_NAME + ":"):
                placement = (name.split(":", 1)[1] if ":" in name
                             else cache_placement)
                if promote_to_device and placement == "host":
                    placement = "device"
                if placement == "device":
                    return Saveable
                if placement == "host":
                    # the host tier holds matrices. A vector (norm scale,
                    # bias) stays in HBM: its cache is a rounding error,
                    # and stacking its one-row slices into a host buffer
                    # across the layer scan is a DMA the TPU compiler
                    # refuses (sublane-misaligned update)
                    if host_offload and avals[0].ndim >= 2:
                        return Offloadable(src="device", dst="pinned_host")
                    return Saveable
                return Recompute
            if name == FULL_NAME:
                return Recompute
            if name == ACT_NAME:
                if activation_policy == "offload_acts":
                    return Offloadable(src="device", dst="pinned_host")
                return Saveable
            return Recompute
        if activation_policy == "save_all" and s in SAVE_PRIMS:
            return Saveable
        if (activation_policy == "save_collectives"
                and s in COLLECTIVE_SAVE_PRIMS):
            return Saveable
        return Recompute

    return policy


def cache_placement_for_mode(mode) -> str:
    return resolve_strategy(mode).cache_placement


def checkpoint_layer(fn, mode, activation_policy: str = "save_all",
                     host_offload: bool = True, placement: Optional[str] = None):
    """Wrap a layer-apply function with the FCDP remat policy.

    ``mode`` is a strategy name or ShardingStrategy object (composites
    welcome: each plan's cache mark carries its own placement).
    ``placement='device'`` is the FCDP-Cache segment promotion -- it
    lifts host-placed caches to HBM and leaves other groups alone."""
    pol = make_remat_policy(
        resolve_strategy(mode).cache_placement,
        activation_policy, host_offload,
        promote_to_device=(placement == "device"))
    return jax.checkpoint(fn, policy=pol)

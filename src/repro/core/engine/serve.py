"""Serve-step builders (prefill / decode) and the decode-state
PartitionSpec derivations they share with the dry-run.

Parameter layouts arrive per leaf (``bundle.leaf_specs``), so a served
model may mix strategy groups (per-tensor mixed sharding) -- e.g.
sharded-MoE decode against mics-group expert shards while the dense
trunk serves from the fcdp frozen layout; the scan-level gather
schedule is the GatherScheduler's job either way."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ShapeCell

def serve_batch_dims(bundle, cell: ShapeCell,
                     seq_sharded: bool = False) -> Tuple[int, P]:
    """Batch sharding for serving. When the sequence dimension owns
    'data' (long-context), batch may only use the remaining fsdp axes."""
    mi = bundle.mi
    axes = tuple(a for a in mi.fsdp_axes
                 if not (seq_sharded and a == mi.seq_axis))
    deg = 1
    for a in axes:
        deg *= mi.size(a)
    if axes and cell.global_batch % deg == 0:
        return cell.global_batch // deg, P(axes)
    return cell.global_batch, P()


def swap_adapters(bundle, params_leaves, adapter_leaves):
    """Adapter hot-swap over one cached base model: replace ONLY the
    trainable (adapter) leaves of a served parameter set, keeping the
    frozen trunk's leaves -- and hence its residency (pod-replicated /
    host-cached, zero steady-state DCN bytes) -- untouched. The swap is
    a flat-index splice, so no base-weight gather or re-layout runs;
    only the adapters' own (DCN-crossing) leaves are new arrays.

    bundle: a PEFT StepBundle (``sys.peft=True``). params_leaves: flat
    leaf list as the serve steps consume. adapter_leaves: new values for
    the bundle's trainable leaves, in ``bundle.train_idx`` order."""
    if len(adapter_leaves) != len(bundle.train_idx):
        raise ValueError(
            f"adapter hot-swap expects {len(bundle.train_idx)} trainable "
            f"leaves, got {len(adapter_leaves)}")
    out = list(params_leaves)
    for i, v in zip(bundle.train_idx, adapter_leaves):
        out[i] = v
    return out


def build_prefill_step(bundle):
    run, mesh = bundle.run, bundle.mesh
    model = bundle.model
    cell = run.shape
    b_local, bspec = serve_batch_dims(bundle, cell)
    cfg = run.model

    if cfg.num_encoder_layers > 0:
        def body(params_leaves, enc_embeds, ids, state):
            params = jax.tree.unflatten(bundle.treedef, params_leaves)
            return model.prefill_fn(params, enc_embeds, ids, state)
    else:
        def body(params_leaves, ids, state):
            params = jax.tree.unflatten(bundle.treedef, params_leaves)
            return model.prefill_fn(params, ids, state)

    st_specs = state_specs(bundle, cell, seq_sharded=False)
    logits_spec = P(bspec[0] if len(bspec) else None, "model")
    if cfg.num_encoder_layers > 0:
        in_specs = (bundle.leaf_specs, bspec, bspec, st_specs)
    else:
        in_specs = (bundle.leaf_specs, bspec, st_specs)
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=(logits_spec, st_specs))
    return jax.jit(fn, donate_argnums=(2,) if cfg.num_encoder_layers == 0
                   else (3,))


def build_decode_step(bundle, seq_sharded: bool = False):
    run, mesh = bundle.run, bundle.mesh
    model = bundle.model
    cell = run.shape
    b_local, bspec = serve_batch_dims(bundle, cell, seq_sharded)

    def body(params_leaves, tok, state):
        params = jax.tree.unflatten(bundle.treedef, params_leaves)
        return model.decode_fn(params, tok, state,
                               seq_sharded=seq_sharded)

    st_specs = state_specs(bundle, cell, seq_sharded)
    logits_spec = P(bspec[0] if len(bspec) else None, "model")
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(bundle.leaf_specs, bspec, st_specs),
                       out_specs=(logits_spec, st_specs))
    return jax.jit(fn, donate_argnums=(2,))


def state_specs(bundle, cell: ShapeCell, seq_sharded: bool):
    """PartitionSpec tree matching init_decode_state's structure.

    States carry GLOBAL logical shapes; these specs slice them:
      - batch dim (1, after the stack dim) over the fsdp axes
      - kv-cache seq dim over 'data' when seq_sharded (long-context)
      - TP-owned dims ('model'): rwkv heads, mamba d_inner channels
    """
    _, bspec = serve_batch_dims(bundle, cell, seq_sharded)
    batch_axes = bspec[0] if len(bspec) else None
    example = abstract_state(bundle, cell, seq_sharded)
    return _specs_for_state(bundle, example, batch_axes, seq_sharded)


def _specs_for_state(bundle, example, batch_axes, seq_sharded: bool):
    mi = bundle.mi
    paths, treedef = jax.tree.flatten_with_path(example)
    specs = []
    for path, arr in paths:
        keys = [str(getattr(k, "key", getattr(k, "idx", k)))
                for k in path]
        name = keys[-1]
        kind = keys[-2] if len(keys) >= 2 else ""
        nd = arr.ndim
        ent = [None] * nd
        if nd >= 2 and batch_axes is not None:
            ent[1] = batch_axes
        if kind in ("attn", "xattn") and name in ("k", "v"):
            if seq_sharded and kind == "attn":
                ent[2] = mi.seq_axis   # batch axes already exclude it
            elif kind == "attn" and nd >= 4 and mi.tp > 1:
                ent[3] = "model"       # TP-sharded kv-head slots
        elif kind == "mamba":
            if name == "conv" and nd >= 4:
                ent[3] = "model"
            elif name == "h" and nd >= 3:
                ent[2] = "model"
        elif kind == "rwkv_tm" and name == "s" and nd >= 3:
            ent[2] = "model"
        specs.append(P(*ent))
    return jax.tree.unflatten(treedef, specs)


def abstract_state(bundle, cell: ShapeCell, seq_sharded: bool):
    cfg = bundle.run.model
    kw = {}
    if cfg.num_encoder_layers > 0:
        kw["enc_len"] = max(cell.seq_len // 4, 8)
    return jax.eval_shape(
        lambda: bundle.model.init_decode_state(
            cell.global_batch, cell.seq_len, seq_sharded=seq_sharded,
            **kw))


# ===========================================================================
# Paged-KV serve path (continuous batching; see core/kv_cache.py)
# ===========================================================================

def check_paged_plan(model) -> None:
    """The paged path is gated to attention-only mixer stacks: MoE
    dispatch couples batch rows through capacity dropping (breaking
    per-request bit-identity) and the recurrent mixers (mamba/rwkv)
    have no paged state."""
    bad = sorted({k for kinds in model.plan for k in kinds
                  if k not in ("attn", "mlp")})
    if bad:
        raise ValueError(
            f"paged serving supports (attn, mlp) stacks only, plan has "
            f"{bad}; use the single-request contiguous path instead")


def paged_replicas(bundle, cell: ShapeCell) -> int:
    """Data replicas the paged pool's page dim is split over (1 when
    the batch falls back to replicated P())."""
    b_local, _ = serve_batch_dims(bundle, cell)
    return cell.global_batch // b_local


def paged_pages_global(bundle, cell: ShapeCell, kv) -> int:
    return kv.pages_per_replica * paged_replicas(bundle, cell)


def default_paged_kv(bundle, cell: ShapeCell):
    """A pool sized so every batch slot can hold one max-length
    (cell.seq_len) sequence -- the capacity-neutral default matching
    the contiguous cache's footprint, plus the scratch page."""
    from repro.core.kv_cache import PagedKVConfig
    ps = 16 if cell.seq_len % 16 == 0 else 8
    mpps = -(-cell.seq_len // ps)
    slots = cell.global_batch // paged_replicas(bundle, cell)
    return PagedKVConfig(page_size=ps,
                         pages_per_replica=1 + slots * mpps,
                         max_pages_per_seq=mpps)


def paged_state_specs(bundle, cell: ShapeCell, kv):
    """Specs for the paged pools: page dim over the batch fsdp axes,
    kv-slot dim over 'model' -- the same positional rules as the
    contiguous state (the paged leaves are named k/v under attn too)."""
    _, bspec = serve_batch_dims(bundle, cell)
    batch_axes = bspec[0] if len(bspec) else None
    example = abstract_paged_state(bundle, cell, kv)
    return _specs_for_state(bundle, example, batch_axes,
                            seq_sharded=False)


def abstract_paged_state(bundle, cell: ShapeCell, kv):
    n_pages = paged_pages_global(bundle, cell, kv)
    return jax.eval_shape(
        lambda: bundle.model.init_paged_state(n_pages, kv.page_size))


def build_paged_decode_step(bundle, kv):
    """One continuous-batching decode step: (params, tok [B,1], table
    [B,max_pages], lengths [B], pools) -> (logits [B,V], pools)."""
    run, mesh = bundle.run, bundle.mesh
    model = bundle.model
    check_paged_plan(model)
    cell = run.shape
    _, bspec = serve_batch_dims(bundle, cell)

    def body(params_leaves, tok, table, lengths, state):
        params = jax.tree.unflatten(bundle.treedef, params_leaves)
        return model.paged_decode_fn(params, tok, state, table, lengths)

    st_specs = paged_state_specs(bundle, cell, kv)
    logits_spec = P(bspec[0] if len(bspec) else None, "model")
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(bundle.leaf_specs, bspec, bspec, bspec,
                                 st_specs),
                       out_specs=(logits_spec, st_specs))
    return jax.jit(fn, donate_argnums=(4,))


def build_prefill_chunk_step(bundle, kv):
    """One chunked-prefill step: (params, ids [B,C], table, pos0 [B],
    last_idx [B], pools) -> (last-prompt-token logits [B,V], pools).
    C is whatever the caller feeds (jit caches per chunk size); rows not
    prefilling this call must carry a scratch (all-zero) table row."""
    run, mesh = bundle.run, bundle.mesh
    model = bundle.model
    check_paged_plan(model)
    cell = run.shape
    _, bspec = serve_batch_dims(bundle, cell)

    def body(params_leaves, ids, table, pos0, last_idx, state):
        params = jax.tree.unflatten(bundle.treedef, params_leaves)
        return model.paged_prefill_fn(params, ids, state, table, pos0,
                                      last_idx)

    st_specs = paged_state_specs(bundle, cell, kv)
    logits_spec = P(bspec[0] if len(bspec) else None, "model")
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(bundle.leaf_specs, bspec, bspec, bspec,
                                 bspec, st_specs),
                       out_specs=(logits_spec, st_specs))
    return jax.jit(fn, donate_argnums=(5,))


def build_greedy_pick(bundle):
    """Greedy sampler, jitted ONCE for the whole decode loop: each TP
    rank reduces its local vocab shard to one (value, index) candidate
    and only the tp candidates cross the wire -- never the full [B, V]
    logits. Tie-breaking matches jnp.argmax over the concatenated
    vocab (lowest global index wins)."""
    from repro.compat import all_gather_invariant
    mesh = bundle.mesh
    cell = bundle.run.shape
    mi = bundle.mi
    _, bspec = serve_batch_dims(bundle, cell)

    def body(logits):                       # [b_local, V_local]
        v_loc = jnp.max(logits, axis=-1)
        i_loc = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if mi.tp > 1:
            i_loc = i_loc + jax.lax.axis_index("model") * logits.shape[-1]
            vs = all_gather_invariant(v_loc[None], "model", axis=0,
                                      tiled=True)     # [tp, b_local]
            ix = all_gather_invariant(i_loc[None], "model", axis=0,
                                      tiled=True)
            r = jnp.argmax(vs, axis=0)                # lowest rank on ties
            return jnp.take_along_axis(ix, r[None, :], axis=0)[0]
        return i_loc

    logits_spec = P(bspec[0] if len(bspec) else None, "model")
    out_spec = P(bspec[0] if len(bspec) else None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(logits_spec,),
                       out_specs=out_spec)
    return jax.jit(fn)

"""Sublayer library: ParamDefs + apply functions for every mixer/FFN kind
used by the assigned architectures.

Each sublayer kind K provides:
  K_defs(cfg)                    -> ParamDef tree (unstacked; caller stacks)
  K_apply(cfg, sys, mi, p, x, .) -> output  (train/prefill: full sequence)
  K_decode(...)                  -> (output, new_state) for one-token decode

TP conventions (see DESIGN.md §4):
  attention: q/o head-parallel over 'model' (heads padded), k/v replicated
  mlp:       in/gate column-parallel, out row-parallel (+psum)
  moe:       experts sharded over 'model' (EP), all_to_all dispatch
  mamba:     d_inner channel-parallel, B/C psum'd
  rwkv:      heads padded + head-parallel; channel-mix column-parallel
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compat import all_gather_invariant

from repro.configs.base import ModelConfig, SystemConfig
from repro.core.partition import ParamDef
from repro.models import attention as attn_mod
from repro.models.common import (MeshInfo, local_head_mask, pad_heads,
                                 psum_tp, psum_tp_act, tp_rank)
from repro.models import layers
from repro.models.layers import act_fn, rms_norm

BF16 = jnp.bfloat16


# ===========================================================================
# Attention
# ===========================================================================

def attn_defs(cfg: ModelConfig, tp: int) -> Dict[str, ParamDef]:
    hd = cfg.resolved_head_dim()
    hp = pad_heads(cfg.num_heads, tp)
    d = cfg.d_model
    kvd = cfg.num_kv_heads * hd
    out: Dict[str, ParamDef] = {
        "wq": ParamDef((d, hp * hd), ("fsdp", "tp")),
        "wk": ParamDef((d, kvd), ("fsdp", None)),
        "wv": ParamDef((d, kvd), ("fsdp", None)),
        "wo": ParamDef((hp * hd, d), ("tp", "fsdp"), fusable=True),
        "norm": ParamDef((d,), ("fsdp",), init="ones"),
    }
    if cfg.qkv_bias:
        out["bq"] = ParamDef((hp * hd,), ("tp",), init="zeros")
        out["bk"] = ParamDef((kvd,), (None,), init="zeros")
        out["bv"] = ParamDef((kvd,), (None,), init="zeros")
    if cfg.frontend == "vq_image":  # chameleon uses qk-norm
        out["q_norm"] = ParamDef((hd,), (None,), init="ones")
        out["k_norm"] = ParamDef((hd,), (None,), init="ones")
    return out


def _lora_kwargs(sys, p) -> Dict[str, Any]:
    """Adapter leaves riding in the sublayer dict + the alpha/rank scale
    (single source of truth: SystemConfig.lora_alpha via
    core.peft.lora_scale)."""
    lora = {k: v for k, v in p.items() if "_lora_" in k} or None
    if lora is None:
        return {}
    from repro.core.peft import lora_scale
    return {"lora": lora, "lora_alpha": lora_scale(sys)}


def attn_apply(cfg, sys: SystemConfig, mi: MeshInfo, p, x, positions,
               causal: bool = True, kv_cache=None, xa_kv=None):
    from repro.models.common import tp_region_in
    h = tp_region_in(rms_norm(x, p["norm"], cfg.norm_eps), mi)
    y, new_cache = attn_mod.attention_block(
        h, p["wq"], p["wk"], p["wv"], p["wo"],
        p.get("bq"), p.get("bk"), p.get("bv"),
        cfg, mi, positions, attn_impl=sys.attn_impl,
        kv_cache=kv_cache,
        q_norm=p.get("q_norm"), k_norm=p.get("k_norm"),
        causal=causal, **_lora_kwargs(sys, p))
    return x + y, new_cache


def attn_init_state(cfg, mi: MeshInfo, batch: int, max_len: int,
                    seq_sharded: bool = False):
    """KV cache state with GLOBAL logical shape; sharding is applied by
    the step function's in_specs -- inside shard_map the local slice
    appears.

    Default layout: TP-sharded by kv-head span -- each 'model' rank stores
    only the kv_span(h_local, n_rep, n_kv) heads its q heads read, so the
    global kv-slot dim is tp*span (sharded over 'model'). For the
    seq-sharded long-context layout the cache keeps all kv heads and
    shards the sequence dim over 'data' instead."""
    from repro.models.attention import kv_span
    hd = cfg.resolved_head_dim()
    n_kv = cfg.num_kv_heads
    if seq_sharded:
        shape = (batch, max_len, n_kv, hd)
    else:
        hp = pad_heads(cfg.num_heads, mi.tp)
        h_local = hp // mi.tp
        n_rep = hp // n_kv
        span = kv_span(h_local, n_rep, n_kv)
        shape = (batch, max_len, mi.tp * span, hd)
    return {"k": jnp.zeros(shape, BF16), "v": jnp.zeros(shape, BF16),
            "idx": jnp.zeros((), jnp.int32)}


def attn_init_paged_state(cfg, mi: MeshInfo, n_pages: int, page_size: int):
    """Paged KV pool with GLOBAL logical shape: [n_pages, page_size,
    tp*span, hd]. The page dim is sharded over the batch's fsdp axes
    (per-replica sub-pools -- each data replica owns only its own
    sequences' pages), the slot dim over 'model' exactly like the
    contiguous cache. Page 0 of every replica is the reserved scratch
    page (see core/kv_cache.py)."""
    from repro.models.attention import kv_span
    hd = cfg.resolved_head_dim()
    n_kv = cfg.num_kv_heads
    hp = pad_heads(cfg.num_heads, mi.tp)
    h_local = hp // mi.tp
    n_rep = hp // n_kv
    span = kv_span(h_local, n_rep, n_kv)
    shape = (n_pages, page_size, mi.tp * span, hd)
    return {"k": jnp.zeros(shape, BF16), "v": jnp.zeros(shape, BF16)}


def attn_paged(cfg, sys, mi: MeshInfo, p, x, state, positions, table,
               prefill: bool = False):
    """Attention over the paged KV cache (continuous batching): one
    decode token (x: [B,1,D]) or one prefill chunk (x: [B,C,D]) per
    call. positions: [B,S] per-row absolute positions; table: [B,
    max_pages] local page ids. Mirrors attn_apply (prefill) /
    attn_decode (decode) op-for-op so per-request numerics are
    bit-identical to the single-request contiguous-cache path."""
    from repro.models.common import tp_region_in
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    if prefill:
        h = tp_region_in(h, mi)
    y, (pk, pv) = attn_mod.attention_block(
        h, p["wq"], p["wk"], p["wv"], p["wo"],
        p.get("bq"), p.get("bk"), p.get("bv"), cfg, mi, positions,
        paged_kv=(state["k"], state["v"], table),
        q_norm=p.get("q_norm"), k_norm=p.get("k_norm"),
        **_lora_kwargs(sys, p))
    return x + y, {"k": pk, "v": pv}


def attn_decode(cfg, sys, mi: MeshInfo, p, x, state, seq_sharded: bool = False):
    """One-token decode. x: [B,1,D]."""
    pos = state["idx"][None, None]  # [1,1] absolute position
    if not seq_sharded:
        kv = (state["k"], state["v"], state["idx"])
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        y, (k_new, v_new, idx_new) = attn_mod.attention_block(
            h, p["wq"], p["wk"], p["wv"], p["wo"],
            p.get("bq"), p.get("bk"), p.get("bv"), cfg, mi, pos,
            kv_cache=kv, q_norm=p.get("q_norm"), k_norm=p.get("k_norm"),
            **_lora_kwargs(sys, p))
        return x + y, {"k": k_new, "v": v_new, "idx": idx_new}
    # sequence-sharded cache (long_500k): write lands on owner shard
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    B, S, D = h.shape
    hd = cfg.resolved_head_dim()
    hp = pad_heads(cfg.num_heads, mi.tp)
    h_local = hp // mi.tp
    q = (h @ p["wq"])
    if p.get("bq") is not None:
        q = q + p["bq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if p.get("bk") is not None:
        k = k + p["bk"]
    if p.get("bv") is not None:
        v = v + p["bv"]
    q = q.reshape(B, 1, h_local, hd)
    k = k.reshape(B, 1, cfg.num_kv_heads, hd)
    v = v.reshape(B, 1, cfg.num_kv_heads, hd)
    if p.get("q_norm") is not None:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = attn_mod.apply_rope_heads(q, pos, cfg.rope_theta)
    k = attn_mod.apply_rope_heads(k, pos, cfg.rope_theta)
    # write k,v into the shard that owns position idx
    S_local = state["k"].shape[1]
    shard = state["idx"] // S_local
    off = state["idx"] % S_local
    seq_ax = mi.seq_axis
    my_shard = jax.lax.axis_index(seq_ax)
    k_upd = jax.lax.dynamic_update_slice_in_dim(
        state["k"], k.astype(state["k"].dtype), off, axis=1)
    v_upd = jax.lax.dynamic_update_slice_in_dim(
        state["v"], v.astype(state["v"].dtype), off, axis=1)
    is_mine = (shard == my_shard)
    k_cache = jnp.where(is_mine, k_upd, state["k"])
    v_cache = jnp.where(is_mine, v_upd, state["v"])
    # valid length within this shard
    valid = jnp.clip((state["idx"] + 1) - my_shard * S_local, 0, S_local)
    # expand q heads to padded-global mapping handled inside:
    n_rep = hp // cfg.num_kv_heads
    k_exp, v_exp = attn_mod.slice_expand_kv(k_cache, v_cache, h_local,
                                            n_rep, mi)
    out = attn_mod.seq_sharded_decode_attention(
        q, k_exp, v_exp, valid, mi, seq_ax)
    mask = local_head_mask(mi, hp, cfg.num_heads)
    out = out * mask[None, None, :, None].astype(out.dtype)
    y = layers.matmul(out.reshape(B, 1, h_local * hd), p["wo"])
    y = psum_tp(y, mi)
    return x + y, {"k": k_cache, "v": v_cache, "idx": state["idx"] + 1}


# ===========================================================================
# Cross-attention (encoder-decoder)
# ===========================================================================

def xattn_defs(cfg: ModelConfig, tp: int) -> Dict[str, ParamDef]:
    d = attn_defs(cfg, tp)
    d.pop("bq", None); d.pop("bk", None); d.pop("bv", None)
    d.pop("q_norm", None); d.pop("k_norm", None)
    return d


def xattn_init_state(cfg, mi: MeshInfo, batch: int, enc_len: int):
    hd = cfg.resolved_head_dim()
    shape = (batch, enc_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, BF16), "v": jnp.zeros(shape, BF16)}


def xattn_apply(cfg, sys, mi: MeshInfo, p, x, enc_kv):
    """enc_kv: (k, v) precomputed from encoder output: [B,Senc,KVH,hd]."""
    B, S, D = x.shape
    hd = cfg.resolved_head_dim()
    hp = pad_heads(cfg.num_heads, mi.tp)
    h_local = hp // mi.tp
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(B, S, h_local, hd)
    k, v = enc_kv
    n_rep = hp // cfg.num_kv_heads
    k_exp, v_exp = attn_mod.slice_expand_kv(k, v, h_local, n_rep, mi)
    out = attn_mod.chunked_causal_attention(q, k_exp, v_exp, causal=False)
    mask = local_head_mask(mi, hp, cfg.num_heads)
    out = out * mask[None, None, :, None].astype(out.dtype)
    y = layers.matmul(out.reshape(B, S, h_local * hd), p["wo"])
    return x + psum_tp(y, mi), None


def xattn_make_kv(cfg, mi: MeshInfo, p, enc_out):
    """Project encoder output once into this cross-attn layer's K/V."""
    B, S, D = enc_out.shape
    hd = cfg.resolved_head_dim()
    k = (enc_out @ p["wk"]).reshape(B, S, cfg.num_kv_heads, hd)
    v = (enc_out @ p["wv"]).reshape(B, S, cfg.num_kv_heads, hd)
    return k, v


# ===========================================================================
# Dense MLP (GLU or plain)
# ===========================================================================

def mlp_defs(cfg: ModelConfig, tp: int, d_ff: Optional[int] = None) -> Dict[str, ParamDef]:
    d, f = cfg.d_model, (d_ff or cfg.d_ff)
    out = {
        "w_in": ParamDef((d, f), ("fsdp", "tp")),
        "w_out": ParamDef((f, d), ("tp", "fsdp"), fusable=True),
        "norm": ParamDef((d,), ("fsdp",), init="ones"),
    }
    if cfg.act in ("swiglu", "geglu"):
        out["w_gate"] = ParamDef((d, f), ("fsdp", "tp"))
    return out


def mlp_apply(cfg, sys, mi: MeshInfo, p, x):
    from repro.models.common import tp_region_in
    h = tp_region_in(rms_norm(x, p["norm"], cfg.norm_eps), mi)
    if "w_gate" in p:
        z = act_fn(cfg.act)(h @ p["w_gate"]) * (h @ p["w_in"])
    else:
        z = act_fn(cfg.act)(h @ p["w_in"])
    y = layers.matmul(z, p["w_out"])
    return x + psum_tp_act(y, mi)


# ===========================================================================
# MoE (GShard-style capacity dispatch, EP over 'model')
# ===========================================================================

def moe_defs(cfg: ModelConfig, tp: int,
             weight_resident: bool = False) -> Dict[str, ParamDef]:
    """Expert weights: EP over 'model'; ZeRO over (pod,data) by default.

    weight_resident (beyond-paper): per-step expert-weight gather volume
    (E_local*3*d*fe bytes per layer, fwd+bwd) usually exceeds the resident
    size by 10x+ at decode/small-batch shapes, so ZeRO-shard them over the
    pod axis only and keep the intra-pod shard resident in HBM.
    """
    m = cfg.moe
    d, fe, e = cfg.d_model, m.d_ff_expert, m.num_experts
    scope = "inter_only" if weight_resident else "full"
    out = {
        "router": ParamDef((d, e), ("fsdp", None), init_scale=0.1),
        "we_in": ParamDef((e, d, fe), ("tp", "fsdp", None), fsdp_scope=scope),
        "we_gate": ParamDef((e, d, fe), ("tp", "fsdp", None),
                            fsdp_scope=scope),
        "we_out": ParamDef((e, fe, d), ("tp", None, "fsdp"),
                           fsdp_scope=scope),
        "norm": ParamDef((cfg.d_model,), ("fsdp",), init="ones"),
    }
    return out


def _dispatch_indices(eid_flat, num_experts: int, capacity: int):
    """Position of each (token,slot) within its expert's capacity buffer."""
    n = eid_flat.shape[0]
    order = jnp.argsort(eid_flat, stable=True)
    sorted_e = eid_flat[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - first.astype(jnp.int32)
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < capacity
    return pos, keep


def _moe_chunk(x_flat, p, cfg, mi: MeshInfo, capacity: int):
    """x_flat: [T, D] tokens; returns ([T, D], aux_loss_sum)."""
    m = cfg.moe
    E, k = m.num_experts, m.top_k
    T, D = x_flat.shape
    logits = (x_flat @ p["router"]).astype(jnp.float32)       # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eid = jax.lax.top_k(probs, k)                  # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    eid_flat = eid.reshape(-1)                                # [T*k]
    pos, keep = _dispatch_indices(eid_flat, E, capacity)
    # scatter tokens into [E+1, C, D]; dropped slots go to the dummy row
    e_idx = jnp.where(keep, eid_flat, E)
    x_slots = jnp.repeat(x_flat, k, axis=0)                   # [T*k, D]
    buf = jnp.zeros((E + 1, capacity, D), x_flat.dtype)
    buf = buf.at[e_idx, jnp.where(keep, pos, 0)].set(
        jnp.where(keep[:, None], x_slots, 0))
    buf = buf[:E]                                             # [E, C, D]
    # EP all_to_all over 'model': [E, C, D] -> [E_local, tp*C, D]
    if mi.tp >= 1:
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)
    h = jnp.einsum("ecd,edf->ecf", buf, p["we_in"])
    g = jnp.einsum("ecd,edf->ecf", buf, p["we_gate"])
    z = act_fn(cfg.act)(g) * h
    y = jnp.einsum("ecf,efd->ecd", z, p["we_out"])
    if mi.tp >= 1:
        y = jax.lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                               tiled=True)                    # [E, C, D]
    # combine
    gathered = y[jnp.where(keep, eid_flat, 0), jnp.where(keep, pos, 0)]
    gathered = jnp.where(keep[:, None], gathered, 0)
    out = jnp.sum(gathered.reshape(T, k, D)
                  * gate_vals[..., None].astype(y.dtype), axis=1)
    # load-balance aux loss (GShard): E * sum_e f_e * p_e
    ones = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], eid].set(1.0)
    f_e = jnp.mean(ones, axis=0) / k
    p_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * p_e) * T                          # sum-scaled
    return out, aux


def _moe_chunk_sharded(x_flat, p, cfg, mi: MeshInfo, capacity: int,
                       we_plans=None):
    """Gather-free expert compute for decode: expert weights stay in
    their sharded storage (fsdp axes on the d_model dims); the (tiny)
    token buffers are all-gathered over those axes instead, partials are
    contraction-psum'd, and each rank keeps its own token block. Moves
    MBs of activations instead of GBs of weights per layer.

    p carries raw we_* shards plus their GatherPlans under '_we_plans'.
    """
    m = cfg.moe
    E, k = m.num_experts, m.top_k
    T, D = x_flat.shape
    plans = we_plans
    waxes = tuple(plans["we_in"].inter_axes) + tuple(plans["we_in"].intra_axes)
    # single shard axis only (the frozen serving layout: intra=('data',));
    # multi-axis would need spec-major block ordering in the reassembly
    assert len(waxes) <= 1, f"sharded MoE compute expects <=1 axis, {waxes}"
    n_w = 1
    for a in waxes:
        n_w *= mi.size(a)

    logits = (x_flat @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eid = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    eid_flat = eid.reshape(-1)
    pos, keep = _dispatch_indices(eid_flat, E, capacity)
    e_idx = jnp.where(keep, eid_flat, E)
    x_slots = jnp.repeat(x_flat, k, axis=0)
    buf = jnp.zeros((E + 1, capacity, D), x_flat.dtype)
    buf = buf.at[e_idx, jnp.where(keep, pos, 0)].set(
        jnp.where(keep[:, None], x_slots, 0))
    buf = buf[:E]
    # EP all_to_all over 'model'
    buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                             tiled=True)                  # [E_loc, tp*C, D]
    if waxes:
        # share tokens across the weight-shard ranks (tiny at decode)
        for a in waxes:
            buf = all_gather_invariant(buf, a, axis=1, tiled=True)
        my = 0
        for a in waxes:
            my = my * mi.size(a) + jax.lax.axis_index(a)
        C_here = buf.shape[1]
        # partial contraction over this rank's d_model slice
        d_loc = p["we_in"].shape[1]
        off = my * d_loc
        buf_slice = jax.lax.dynamic_slice_in_dim(buf, off, d_loc, axis=2)
        h = jnp.einsum("ecd,edf->ecf", buf_slice, p["we_in"])
        g = jnp.einsum("ecd,edf->ecf", buf_slice, p["we_gate"])
        h = jax.lax.psum(h, waxes)
        g = jax.lax.psum(g, waxes)
        z = act_fn(cfg.act)(g) * h
        # we_out sharded on its OUTPUT (d_model) dim: local columns + AG
        y_loc = jnp.einsum("ecf,efd->ecd", z, p["we_out"])
        y = y_loc
        for a in waxes:
            y = all_gather_invariant(y, a, axis=2, tiled=True)
        # keep this rank's token block
        y = jax.lax.dynamic_slice_in_dim(
            y, my * (C_here // n_w), C_here // n_w, axis=1)
    else:  # weights fully resident: plain local compute
        h = jnp.einsum("ecd,edf->ecf", buf, p["we_in"])
        g = jnp.einsum("ecd,edf->ecf", buf, p["we_gate"])
        z = act_fn(cfg.act)(g) * h
        y = jnp.einsum("ecf,efd->ecd", z, p["we_out"])
    y = jax.lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                           tiled=True)                    # [E, C, D]
    gathered = y[jnp.where(keep, eid_flat, 0), jnp.where(keep, pos, 0)]
    gathered = jnp.where(keep[:, None], gathered, 0)
    out = jnp.sum(gathered.reshape(T, k, D)
                  * gate_vals[..., None].astype(y.dtype), axis=1)
    return out, jnp.float32(0)


def moe_apply(cfg, sys, mi: MeshInfo, p, x, sharded: bool = False):
    """x: [B, S, D]. Tokens are split over the 'model' axis before
    dispatch (activations are TP-replicated; without the split every rank
    would dispatch the same tokens -- tp-fold redundant expert compute),
    then combined with an all-gather. Chunked dispatch bounds [E,C,D].
    sharded=True (decode): gather-free expert compute, see
    _moe_chunk_sharded."""
    m = cfg.moe
    B, S, D = x.shape
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    h_flat = h.reshape(B * S, D)
    T_orig = B * S
    # pad tokens to a multiple of tp so every rank dispatches a distinct
    # slice (single code path; padding outputs are sliced away after the
    # invariant gather)
    T_pad = -(-T_orig // mi.tp) * mi.tp
    if T_pad != T_orig:
        h_flat = jnp.pad(h_flat, ((0, T_pad - T_orig), (0, 0)))
    rank = tp_rank(mi)
    T = T_pad // mi.tp
    h_flat = jax.lax.dynamic_slice_in_dim(h_flat, rank * T, T, axis=0)
    tok_gathered = True
    chunk = min(getattr(sys, "moe_token_chunk", 8192), T)
    n = T // chunk if T % chunk == 0 else 1
    if n == 1:
        chunk = T
    capacity = int(math.ceil(chunk * m.top_k / m.num_experts
                             * m.capacity_factor))
    capacity = max(4, ((capacity + 3) // 4) * 4)
    # inner remat: dispatch buffers/sorts recomputed in backward.
    # GatherPlans are static metadata -- keep them out of the checkpoint
    # arguments (closure capture instead).
    we_plans = p.pop("_we_plans", None)
    if sharded:
        chunk_fn = lambda xc, pp: _moe_chunk_sharded(
            xc, pp, cfg, mi, capacity, we_plans)
    else:
        chunk_fn = lambda xc, pp: _moe_chunk(xc, pp, cfg, mi, capacity)
    moe_fn = jax.checkpoint(
        chunk_fn, policy=jax.checkpoint_policies.nothing_saveable)
    if n == 1:
        out, aux = moe_fn(h_flat, p)
    else:
        def body(carry, xc):
            out_c, aux_c = moe_fn(xc, p)
            return carry + aux_c, out_c
        from repro.models.common import pvary_like
        aux0 = pvary_like(jnp.float32(0), h_flat)
        aux, outs = jax.lax.scan(
            body, aux0, h_flat.reshape(n, chunk, D))
        out = outs.reshape(T, D)
    # invariant gather: every rank reconstructs the same full token set
    out = all_gather_invariant(out, "model", axis=0, tiled=True)
    aux = jax.lax.psum(aux, "model")
    out = out[:T_orig]
    y = out.reshape(B, S, D).astype(x.dtype)
    return x + y, aux * m.aux_loss_weight


# ===========================================================================
# Mamba (selective scan; for Jamba)
# ===========================================================================

def mamba_defs(cfg: ModelConfig, tp: int) -> Dict[str, ParamDef]:
    mc = cfg.mamba
    d = cfg.d_model
    d_in = mc.expand * d
    dt_rank = mc.dt_rank or -(-d // 16)
    ns = mc.d_state
    return {
        "norm": ParamDef((d,), ("fsdp",), init="ones"),
        "in_proj": ParamDef((d, 2 * d_in), ("fsdp", "tp")),
        "conv_w": ParamDef((d_in, mc.d_conv), ("tp", None), init_scale=0.5),
        "conv_b": ParamDef((d_in,), ("tp",), init="zeros"),
        "x_proj": ParamDef((d_in, dt_rank + 2 * ns), ("tp", None)),
        "dt_proj": ParamDef((dt_rank, d_in), (None, "tp")),
        "dt_bias": ParamDef((d_in,), ("tp",), init="zeros"),
        "A_log": ParamDef((d_in, ns), ("tp", None), init="ones"),
        "D_skip": ParamDef((d_in,), ("tp",), init="ones"),
        "out_proj": ParamDef((d_in, d), ("tp", "fsdp"), fusable=True),
    }


def _mamba_core(cfg, mi, p, xz, conv_state=None, h_state=None, chunk=512):
    """xz: [B, S, 2*d_in_local]. Returns (y_local [B,S,d_in_local], states)."""
    mc = cfg.mamba
    ns = mc.d_state
    dt_rank = mc.dt_rank or -(-cfg.d_model // 16)
    B, S, _ = xz.shape
    d_loc = xz.shape[-1] // 2
    x, z = jnp.split(xz, 2, axis=-1)
    # causal depthwise conv (k = d_conv)
    k = mc.d_conv
    if conv_state is None:
        x_pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        x_pad = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    new_conv_state = x_pad[:, -(k - 1):, :] if k > 1 else None
    idx = jnp.arange(S)[:, None] + jnp.arange(k)[None, :]
    xs = x_pad[:, idx]                                    # [B,S,k,dloc]
    xc = jnp.einsum("bskd,dk->bsd", xs, p["conv_w"]) + p["conv_b"]
    xc = jax.nn.silu(xc)
    # projections: B,C are global (psum over model); dt per-channel local
    xdb = xc @ p["x_proj"]                                # [B,S,r+2n] partial
    xdb = psum_tp(xdb, mi)
    dt_in, Bc, Cc = jnp.split(xdb, [dt_rank, dt_rank + ns], axis=-1)
    dt = jax.nn.softplus(dt_in @ p["dt_proj"] + p["dt_bias"])  # [B,S,dloc]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))          # [dloc, ns]
    a = jnp.exp(dt.astype(jnp.float32)[..., None] * A)    # [B,S,dloc,ns]
    b = (dt.astype(jnp.float32) * xc.astype(jnp.float32))[..., None] \
        * Bc.astype(jnp.float32)[..., None, :]            # [B,S,dloc,ns]

    def scan_chunk(h0, ab):
        a_c, b_c = ab

        def comb(l, r):
            al, bl = l
            ar, br = r
            return al * ar, br + ar * bl
        a_acc, b_acc = jax.lax.associative_scan(comb, (a_c, b_c), axis=1)
        hs = a_acc * h0[:, None] + b_acc                  # [B,c,dloc,ns]
        return hs[:, -1], hs

    h0 = (jnp.zeros((B, d_loc, ns), jnp.float32)
          if h_state is None else h_state)
    from repro.models.common import pvary_like
    h0 = pvary_like(pvary_like(h0, a), b)
    c = min(chunk, S)
    scan_fn = jax.checkpoint(
        scan_chunk, policy=jax.checkpoint_policies.nothing_saveable)
    if S % c == 0 and S > c:
        n = S // c
        a_r = a.reshape(B, n, c, d_loc, ns).swapaxes(0, 1)
        b_r = b.reshape(B, n, c, d_loc, ns).swapaxes(0, 1)
        h_last, hs = jax.lax.scan(scan_fn, h0, (a_r, b_r))
        hs = hs.swapaxes(0, 1).reshape(B, S, d_loc, ns)
    else:
        h_last, hs = scan_fn(h0, (a, b))
    y = jnp.einsum("bsdn,bsn->bsd", hs, Cc.astype(jnp.float32))
    y = y + p["D_skip"].astype(jnp.float32) * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(xz.dtype)
    return y, (new_conv_state, h_last)


def mamba_apply(cfg, sys, mi: MeshInfo, p, x):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    xz = h @ p["in_proj"]
    y, _ = _mamba_core(cfg, mi, p, xz)
    out = layers.matmul(y, p["out_proj"])
    return x + psum_tp_act(out, mi)


def mamba_prefill(cfg, sys, mi: MeshInfo, p, x):
    """Full-sequence forward that also returns final recurrent state."""
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    xz = h @ p["in_proj"]
    y, (conv_s, h_s) = _mamba_core(cfg, mi, p, xz)
    out = layers.matmul(y, p["out_proj"])
    return x + psum_tp(out, mi), {"conv": conv_s.astype(BF16), "h": h_s}


def mamba_init_state(cfg, mi: MeshInfo, batch: int):
    """Global logical shape; d_inner dim is 'model'-sharded via in_specs."""
    mc = cfg.mamba
    d_in = mc.expand * cfg.d_model
    return {"conv": jnp.zeros((batch, mc.d_conv - 1, d_in), BF16),
            "h": jnp.zeros((batch, d_in, mc.d_state), jnp.float32)}


def mamba_decode(cfg, sys, mi: MeshInfo, p, x, state):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    xz = h @ p["in_proj"]
    y, (conv_s, h_s) = _mamba_core(cfg, mi, p, xz,
                                   conv_state=state["conv"],
                                   h_state=state["h"])
    out = layers.matmul(y, p["out_proj"])
    return x + psum_tp(out, mi), {"conv": conv_s.astype(BF16), "h": h_s}


# ===========================================================================
# RWKV-6 (Finch)
# ===========================================================================

def rwkv_tm_defs(cfg: ModelConfig, tp: int) -> Dict[str, ParamDef]:
    rc = cfg.rwkv
    d = cfg.d_model
    hd = rc.head_dim
    n_heads = d // hd
    hp = pad_heads(n_heads, tp)
    da = hp * hd                        # padded attention width
    lr = rc.decay_lora
    return {
        "norm": ParamDef((d,), ("fsdp",), init="ones"),
        "maa_base": ParamDef((6, d), (None, "fsdp"), init="zeros"),  # x,w,k,v,r,g
        "maa_w1": ParamDef((d, 5 * 32), ("fsdp", None), init="zeros"),
        "maa_w2": ParamDef((5, 32, d), (None, None, "fsdp"), init_scale=0.1),
        "w_r": ParamDef((d, da), ("fsdp", "tp")),
        "w_k": ParamDef((d, da), ("fsdp", "tp")),
        "w_v": ParamDef((d, da), ("fsdp", "tp")),
        "w_g": ParamDef((d, da), ("fsdp", "tp")),
        "decay_base": ParamDef((da,), ("tp",), init="zeros"),
        "decay_w1": ParamDef((d, lr), ("fsdp", None), init="zeros"),
        "decay_w2": ParamDef((lr, da), (None, "tp"), init_scale=0.1),
        "u": ParamDef((da,), ("tp",), init="zeros"),
        "ln_x": ParamDef((da,), ("tp",), init="ones"),
        "w_o": ParamDef((da, d), ("tp", "fsdp"), fusable=True),
    }


def _token_shift(x, xprev_last=None):
    """x: [B,S,D] -> previous-token tensor; xprev_last: [B,D] carry."""
    if xprev_last is None:
        prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    else:
        prev = jnp.concatenate([xprev_last[:, None], x[:, :-1]], axis=1)
    return prev


def _rwkv_mix(p, x, prev):
    """Data-dependent lerp (ddlerp) producing the 5 mixed inputs."""
    dx = prev - x
    mx = x + dx * p["maa_base"][0]
    k5 = jnp.tanh(mx @ p["maa_w1"])                   # [B,S,5*32]
    B, S, _ = k5.shape
    k5 = k5.reshape(B, S, 5, 32)
    deltas = jnp.einsum("bsfr,frd->bsfd", k5, p["maa_w2"])  # [B,S,5,D]
    outs = []
    for i, name in enumerate(("w", "k", "v", "r", "g")):
        mu = p["maa_base"][i + 1] + deltas[:, :, i]
        outs.append(x + dx * mu)
    return outs  # xw, xk, xv, xr, xg


def _wkv_chunked(r, k, v, logw, u, chunk: int = 64,
                 s0=None):
    """RWKV-6 WKV with per-step per-channel decay, chunked.

    r,k,v: [B,S,H,hd]; logw: [B,S,H,hd] (log decay, <=0); u: [H,hd].
    Returns ([B,S,H,hd], final_state [B,H,hd,hd]).
    State recurrence: S = diag(w_t) S + k_t v_t^T;  o_t = r_t (S_prev + u k_t v_t^T)
    """
    B, S, H, hd = r.shape
    c = min(chunk, S)
    assert S % c == 0, f"wkv seq {S} not divisible by chunk {c}"
    n = max(S // c, 1)
    rs = r.reshape(B, n, c, H, hd).swapaxes(0, 1).astype(jnp.float32)
    ks = k.reshape(B, n, c, H, hd).swapaxes(0, 1).astype(jnp.float32)
    vs = v.reshape(B, n, c, H, hd).swapaxes(0, 1).astype(jnp.float32)
    lws = logw.reshape(B, n, c, H, hd).swapaxes(0, 1).astype(jnp.float32)
    uf = u.astype(jnp.float32)

    def body(S0, inp):
        rc, kc, vc, lwc = inp                          # [B,c,H,hd]
        cw = jnp.cumsum(lwc, axis=1)                   # log prod_{j<=t} w_j
        cw_prev = cw - lwc                             # log prod_{j<t}
        # inter-chunk: q_t = r_t * exp(cw_prev)
        q = rc * jnp.exp(cw_prev)
        o_inter = jnp.einsum("bthk,bhkv->bthv", q, S0)
        # intra-chunk: A[t,i] = sum_ch r[t]k[i] exp(cw_prev[t]-cw[i]), i<t.
        # mask the LOG ratio before exponentiation: for i >= t it is a
        # positive log-sum that overflows under strong decay, and
        # inf * 0 would poison the output with NaNs.
        ratio_log = cw_prev[:, :, None] - cw[:, None, :]       # [B,t,i,H,hd]
        tri = jnp.tril(jnp.ones((c, c), jnp.bool_), -1)        # strict: i<t
        ratio_log = jnp.where(tri[None, :, :, None, None], ratio_log, -1e30)
        A = jnp.einsum("bthk,bihk,btihk->bthi", rc, kc, jnp.exp(ratio_log))
        o_intra = jnp.einsum("bthi,bihv->bthv", A, vc)
        # diagonal (current token, u bonus)
        diag = jnp.einsum("bthk,bthk->bth", rc, uf[None, None] * kc)
        o_diag = diag[..., None] * vc
        o = o_inter + o_intra + o_diag
        # state update: S' = diag(exp(cw_c)) S0 + sum_i outer(k_i exp(cw_c-cw_i), v_i)
        cw_c = cw[:, -1]                               # [B,H,hd]
        kd = kc * jnp.exp(cw_c[:, None] - cw)
        S_new = jnp.exp(cw_c)[..., None] * S0 + jnp.einsum(
            "bihk,bihv->bhkv", kd, vc)
        return S_new, o

    S0 = (jnp.zeros((B, H, hd, hd), jnp.float32) if s0 is None else s0)
    from repro.models.common import pvary_like
    S0 = pvary_like(pvary_like(S0, rs), lws)
    Sf, os = jax.lax.scan(body, S0, (rs, ks, vs, lws))
    out = os.swapaxes(0, 1).reshape(B, S, H, hd)
    return out.astype(r.dtype), Sf


def _group_norm_heads(x, scale, eps=1e-5):
    """x: [B,S,H,hd] normalized per head (rwkv ln_x); scale: [H*hd]."""
    B, S, H, hd = x.shape
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (xf.reshape(B, S, H * hd)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _rwkv_tm_core(cfg, mi, p, x, xprev_last=None, s0=None):
    rc = cfg.rwkv
    hd = rc.head_dim
    n_heads = cfg.d_model // hd
    hp = pad_heads(n_heads, mi.tp)
    h_local = hp // mi.tp
    B, S, D = x.shape
    prev = _token_shift(x, xprev_last)
    xw, xk, xv, xr, xg = _rwkv_mix(p, x, prev)
    r = (xr @ p["w_r"]).reshape(B, S, h_local, hd)
    k = (xk @ p["w_k"]).reshape(B, S, h_local, hd)
    v = (xv @ p["w_v"]).reshape(B, S, h_local, hd)
    g = jax.nn.silu(xg @ p["w_g"])
    logw = -jnp.exp(
        (p["decay_base"] + jnp.tanh(xw @ p["decay_w1"]) @ p["decay_w2"]
         ).astype(jnp.float32)).reshape(B, S, h_local, hd)
    u = p["u"].astype(jnp.float32).reshape(h_local, hd)
    wkv_fn = jax.checkpoint(
        lambda r_, k_, v_, w_, u_: _wkv_chunked(r_, k_, v_, w_, u_, s0=s0),
        policy=jax.checkpoint_policies.nothing_saveable)
    out, s_new = wkv_fn(r, k, v, logw, u)
    hmask = local_head_mask(mi, hp, n_heads)
    out = out * hmask[None, None, :, None].astype(out.dtype)
    out = _group_norm_heads(out, p["ln_x"], cfg.norm_eps)
    out = out * g.astype(out.dtype)
    y = layers.matmul(out, p["w_o"])
    return psum_tp(y, mi), (x[:, -1], s_new)


def rwkv_tm_apply(cfg, sys, mi: MeshInfo, p, x):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    y, _ = _rwkv_tm_core(cfg, mi, p, h)
    return x + y


def rwkv_tm_prefill(cfg, sys, mi: MeshInfo, p, x):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    y, (xlast, s_new) = _rwkv_tm_core(cfg, mi, p, h)
    return x + y, {"xprev": xlast.astype(BF16), "s": s_new}


def rwkv_tm_init_state(cfg, mi: MeshInfo, batch: int):
    """Global logical shape; head dim is 'model'-sharded via in_specs."""
    rc = cfg.rwkv
    hd = rc.head_dim
    hp = pad_heads(cfg.d_model // hd, mi.tp)
    return {"xprev": jnp.zeros((batch, cfg.d_model), BF16),
            "s": jnp.zeros((batch, hp, hd, hd), jnp.float32)}


def rwkv_tm_decode(cfg, sys, mi: MeshInfo, p, x, state):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    y, (xlast, s_new) = _rwkv_tm_core(
        cfg, mi, p, h, xprev_last=state["xprev"].astype(h.dtype),
        s0=state["s"])
    return x + y, {"xprev": xlast.astype(BF16), "s": s_new}


def rwkv_cm_defs(cfg: ModelConfig, tp: int) -> Dict[str, ParamDef]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "norm": ParamDef((d,), ("fsdp",), init="ones"),
        "mu_k": ParamDef((d,), ("fsdp",), init="zeros"),
        "mu_r": ParamDef((d,), ("fsdp",), init="zeros"),
        "w_k": ParamDef((d, f), ("fsdp", "tp")),
        "w_v": ParamDef((f, d), ("tp", "fsdp"), fusable=True),
        "w_r": ParamDef((d, d), ("fsdp", "tp")),
    }


def _rwkv_cm_core(cfg, mi, p, x, xprev_last=None):
    B, S, D = x.shape
    prev = _token_shift(x, xprev_last)
    dx = prev - x
    xk = x + dx * p["mu_k"]
    xr = x + dx * p["mu_r"]
    kk = jnp.square(jax.nn.relu(xk @ p["w_k"]))
    kv = layers.matmul(kk, p["w_v"])
    kv = jax.lax.psum_scatter(kv, "model", scatter_dimension=2,
                              tiled=True)                  # [B,S,D/tp]
    gate = jax.nn.sigmoid(xr @ p["w_r"])                   # [B,S,D/tp]
    out = gate * kv
    out = all_gather_invariant(out, "model", axis=2, tiled=True)
    return out, x[:, -1]


def rwkv_cm_apply(cfg, sys, mi: MeshInfo, p, x):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    y, _ = _rwkv_cm_core(cfg, mi, p, h)
    return x + y


def rwkv_cm_prefill(cfg, sys, mi: MeshInfo, p, x):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    y, xlast = _rwkv_cm_core(cfg, mi, p, h)
    return x + y, {"xprev": xlast.astype(BF16)}


def rwkv_cm_init_state(cfg, mi: MeshInfo, batch: int):
    return {"xprev": jnp.zeros((batch, cfg.d_model), BF16)}


def rwkv_cm_decode(cfg, sys, mi: MeshInfo, p, x, state):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    y, xlast = _rwkv_cm_core(cfg, mi, p, h,
                             xprev_last=state["xprev"].astype(h.dtype))
    return x + y, {"xprev": xlast.astype(BF16)}

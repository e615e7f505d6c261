"""Attention: GQA/MQA on a fused Pallas flash-attention kernel for
causal training rows on a TPU, a chunked (flash-style) jnp
implementation everywhere else, plus decode paths (batch-sharded KV
and sequence-sharded KV for long-context with partial-softmax psum
reconstruction).

TP layout: q heads column-parallel over 'model' (padded to a multiple of
tp); K/V projections replicated over 'model' (GQA kv-head counts are not
divisible by tp=16 for most assigned archs), ZeRO-sharded like all
params. Padding heads are masked to zero so they neither contribute
output nor receive gradient.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.common import (MeshInfo, local_head_mask, psum_tp,
                                 psum_tp_act)
from repro.runtime.lowerings import ATTENTION_EVENT

NEG_INF = -1e30


def _expand_kv(k, n_rep: int):
    """[B,S,KVH,hd] -> [B,S,KVH*n_rep,hd] by repeating each kv head."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def kv_span(h_local: int, n_rep: int, n_kv: int) -> int:
    """Static count of kv heads one TP rank's q heads touch."""
    if n_rep <= 0:
        return n_kv
    aligned = (h_local % n_rep == 0) or (n_rep % h_local == 0)
    span = max(h_local // n_rep, 1) + (0 if aligned else 1)
    return min(span, n_kv)


def slice_expand_kv(k_all, v_all, h_local: int, n_rep: int, mi: MeshInfo):
    """Produce this TP rank's [B,S,h_local,hd] expanded K/V without ever
    materializing the full expanded tensor: slice the (at most
    ceil((h_local-1)/n_rep)+1) kv heads this rank's q heads map onto,
    expand only those, then slice the exact local head range."""
    n_kv = k_all.shape[2]
    rank_start = jax.lax.axis_index("model") * h_local
    span = kv_span(h_local, n_rep, n_kv)
    kv_first = jnp.minimum(rank_start // n_rep, n_kv - span)
    k_loc = jax.lax.dynamic_slice_in_dim(k_all, kv_first, span, axis=2)
    v_loc = jax.lax.dynamic_slice_in_dim(v_all, kv_first, span, axis=2)
    off = rank_start - kv_first * n_rep
    k_exp = jax.lax.dynamic_slice_in_dim(
        _expand_kv(k_loc, n_rep), off, h_local, axis=2)
    v_exp = jax.lax.dynamic_slice_in_dim(
        _expand_kv(v_loc, n_rep), off, h_local, axis=2)
    return k_exp, v_exp


def chunked_causal_attention(q, k, v, *, q_chunk: int = 1024,
                             kv_chunk: int = 1024, causal: bool = True,
                             softmax_scale: Optional[float] = None,
                             q_offset: int = 0):
    """Flash-style attention in pure jnp: O(chunk^2) live memory.

    q: [B, Sq, H, hd]; k, v: [B, Skv, H, hd] (kv already head-expanded).
    q_offset: absolute position of q[0] relative to k[0] (for prefill
    continuation); causal masking uses absolute positions. May be a
    scalar (all rows share one offset -- the contiguous-cache path) or a
    [B] array (per-row offsets -- the paged continuous-batching path,
    where every sequence in the batch sits at its own position). The
    scalar path lowers exactly as before, so single-request serving is
    bit-identical.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    scale = softmax_scale or (1.0 / math.sqrt(hd))
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq = -(-Sq // q_chunk)
    nk = -(-Skv // kv_chunk)
    # pad to multiples
    if Sq % q_chunk:
        q = jnp.pad(q, ((0, 0), (0, nq * q_chunk - Sq), (0, 0), (0, 0)))
    if Skv % kv_chunk:
        pad = nk * kv_chunk - Skv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qs = q.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 3, 2, 4)  # [nq,B,H,qc,hd]
    ks = k.reshape(B, nk, kv_chunk, H, hd).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(B, nk, kv_chunk, H, hd).transpose(1, 0, 3, 2, 4)

    kv_pos = (jnp.arange(nk * kv_chunk)).reshape(nk, kv_chunk)

    q_off = jnp.asarray(q_offset)

    def q_block(qi_qc):
        qi, qc = qi_qc
        rel = qi * q_chunk + jnp.arange(q_chunk)
        # [B, qc] when q_offset is per-row, [1, qc] for the scalar path
        # (identical broadcast shape to the original scalar code)
        q_pos = (q_off[:, None] + rel[None, :] if q_off.ndim == 1
                 else (q_off + rel)[None, :])

        def kv_body(carry, inp):
            m, l, acc = carry
            kc, vc, kpos = inp
            s = jnp.einsum("bhqd,bhkd->bhqk", qc.astype(jnp.float32),
                           kc.astype(jnp.float32)) * scale
            mask = kpos[None, None, None, :] < Skv  # kv padding
            if causal:
                mask = mask & (kpos[None, None, None, :] <= q_pos[:, None, :, None])
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        from repro.models.common import pvary_like
        m0 = pvary_like(jnp.full((B, H, q_chunk), NEG_INF, jnp.float32), qc)
        m0 = pvary_like(m0, ks)
        l0 = pvary_like(pvary_like(
            jnp.zeros((B, H, q_chunk), jnp.float32), qc), ks)
        a0 = pvary_like(pvary_like(
            jnp.zeros((B, H, q_chunk, hd), jnp.float32), qc), ks)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), (ks, vs, kv_pos))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return out  # [B,H,qc,hd]

    outs = jax.lax.map(q_block, (jnp.arange(nq), qs))        # [nq,B,H,qc,hd]
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, nq * q_chunk, H, hd)
    return out[:, :Sq].astype(q.dtype)


def project(x, w, bias, lora, name, scale):
    """``x @ w`` (+ ``bias``) (+ the adapter's term where ``lora`` has
    one for ``name``). With an adapter, its two products stay in f32 and
    the three terms are summed in f32 and rounded once to x's dtype, as
    the f32 reference computes them: after a few lr-sized updates of B
    the adapter's term lies far below the bf16 step of the product, and
    added to an already rounded product and rounded again it is lost or
    kept by how the compiler fuses the add. ``w`` may be a
    ``core.fcdp.FusedParam``, whose ring product arrives rounded."""
    from repro.core.fcdp import FusedParam
    from repro.models.layers import matmul
    a = lora.get(f"{name}_lora_a") if lora else None
    if a is None:
        y = matmul(x, w)
        return y if bias is None else y + bias
    y = (matmul(x, w).astype(jnp.float32) if isinstance(w, FusedParam)
         else jnp.dot(x, w, preferred_element_type=jnp.float32))
    t = jnp.dot(jnp.dot(x, a, preferred_element_type=jnp.float32),
                lora[f"{name}_lora_b"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    y = y + t * scale
    return (y if bias is None else y + bias).astype(x.dtype)


def attention_block(x, wq, wk, wv, wo, bq, bk, bv, cfg, mi: MeshInfo,
                    positions, attn_impl: str = "pallas",
                    kv_cache: Optional[Tuple] = None,
                    paged_kv: Optional[Tuple] = None,
                    q_norm=None, k_norm=None, lora=None,
                    # adapter scale alpha/rank: callers thread the
                    # resolved value from core.peft.lora_scale(sys)
                    # (source of truth: SystemConfig.lora_alpha); the
                    # default only covers direct lora-less unit calls
                    lora_alpha: float = 2.0, causal: bool = True):
    """Full attention sublayer on local shards.

    x: [B, S, D]. wq: [D, Hpad_local*hd]; wk/wv: [D, KVH*hd] (replicated
    over model); wo: [Hpad_local*hd, D]. Returns ([B,S,D], new_kv).

    paged_kv: (pool_k, pool_v, page_table) -- the paged KV cache path
    for continuous batching. pool_k/pool_v: [n_pages, page_size, span,
    hd] (this rank's kv-head span, this replica's pages); page_table:
    [B, max_pages] LOCAL page ids, where page 0 is the reserved scratch
    page rows of inactive batch slots point at. ``positions`` must then
    be the per-row absolute positions [B, S] (contiguous per row).
    Returns (pool_k, pool_v) as new_kv. Mutually exclusive with
    kv_cache.

    Causal self-attention without a cache runs on the fused
    flash-attention kernel (``kernels.ops.causal_attention_train``)
    where ``attn_impl`` is not 'jnp', the mesh's devices are TPUs (or
    ``attn_impl`` is 'pallas_interpret') and S and hd tile the kernel;
    every other call runs ``chunked_causal_attention``. Each trace of a
    call records the path it took (``runtime/lowerings.py``).
    """
    B, S, D = x.shape
    hd = cfg.resolved_head_dim()
    n_kv = cfg.num_kv_heads
    h_local = wq.shape[1] // hd
    padded_heads = h_local * mi.tp

    q, k, v = (project(x, w, b, lora, n, lora_alpha) for w, b, n
               in ((wq, bq, "wq"), (wk, bk, "wk"), (wv, bv, "wv")))
    q = q.reshape(B, S, h_local, hd)
    k = k.reshape(B, S, n_kv, hd)
    v = v.reshape(B, S, n_kv, hd)
    if q_norm is not None:  # chameleon-style qk-norm
        from repro.models.layers import rms_norm
        q = rms_norm(q, q_norm, cfg.norm_eps)
        k = rms_norm(k, k_norm, cfg.norm_eps)
    q = apply_rope_heads(q, positions, cfg.rope_theta)
    k = apply_rope_heads(k, positions, cfg.rope_theta)

    if padded_heads % n_kv != 0:
        raise ValueError(
            f"padded heads {padded_heads} not divisible by kv heads {n_kv}")
    n_rep = padded_heads // n_kv

    new_cache = None
    if paged_kv is not None:
        pool_k, pool_v, table = paged_kv
        span = pool_k.shape[2]
        if span < n_kv or mi.tp > 1:
            rank_start = (jax.lax.axis_index("model") * h_local
                          if mi.tp > 1 else 0)
            kv_first = jnp.minimum(rank_start // n_rep, n_kv - span)
            k_w = jax.lax.dynamic_slice_in_dim(k, kv_first, span, axis=2)
            v_w = jax.lax.dynamic_slice_in_dim(v, kv_first, span, axis=2)
            off = rank_start - kv_first * n_rep
        else:
            k_w, v_w, off = k, v, 0
        n_pages, page_size = pool_k.shape[0], pool_k.shape[1]
        flat_k = pool_k.reshape(n_pages * page_size, span, hd)
        flat_v = pool_v.reshape(n_pages * page_size, span, hd)
        # absolute position -> flat pool slot through the page table.
        # Positions past the table width (chunk-padding overshoot) are
        # redirected to the scratch page: never read (the causal mask
        # stops at each row's own position), so duplicate writes there
        # may land in any order.
        page_idx = positions // page_size
        in_range = page_idx < table.shape[1]
        pageof = jnp.take_along_axis(
            table, jnp.minimum(page_idx, table.shape[1] - 1), axis=1)
        pageof = jnp.where(in_range, pageof, 0)
        slot = pageof * page_size + positions % page_size          # [B, S]
        flat_idx = slot.reshape(-1)
        flat_k = flat_k.at[flat_idx].set(
            k_w.astype(flat_k.dtype).reshape(B * S, span, hd))
        flat_v = flat_v.at[flat_idx].set(
            v_w.astype(flat_v.dtype).reshape(B * S, span, hd))
        new_cache = (flat_k.reshape(pool_k.shape),
                     flat_v.reshape(pool_v.shape))
        # gather every page a row can address into one contiguous view
        # [B, max_pages*page_size, span, hd]; rows beyond a sequence's
        # written length come from scratch/stale pages and are masked by
        # the per-row causal offset below (finite garbage -> exact zero
        # contribution after the NEG_INF mask, see chunked attention).
        gather_idx = (table[..., None] * page_size
                      + jnp.arange(page_size)[None, None, :]
                      ).reshape(B, table.shape[1] * page_size)
        k_gat = flat_k[gather_idx]
        v_gat = flat_v[gather_idx]
        q_offset = positions[:, 0]
        k_exp = jax.lax.dynamic_slice_in_dim(
            _expand_kv(k_gat, n_rep), off, h_local, axis=2)
        v_exp = jax.lax.dynamic_slice_in_dim(
            _expand_kv(v_gat, n_rep), off, h_local, axis=2)
    elif kv_cache is not None:
        # TP-sharded KV cache: each rank stores only the kv_span heads its
        # q heads read (cache local shape [B, S_max, span, hd]); fresh K/V
        # are sliced before the write so the full cache never materializes.
        k_cache, v_cache, cache_index = kv_cache
        span = k_cache.shape[2]
        if span < n_kv or mi.tp > 1:
            rank_start = (jax.lax.axis_index("model") * h_local
                          if mi.tp > 1 else 0)
            kv_first = jnp.minimum(rank_start // n_rep, n_kv - span)
            k_w = jax.lax.dynamic_slice_in_dim(k, kv_first, span, axis=2)
            v_w = jax.lax.dynamic_slice_in_dim(v, kv_first, span, axis=2)
            off = rank_start - kv_first * n_rep
        else:
            k_w, v_w, off = k, v, 0
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_w.astype(k_cache.dtype), cache_index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_w.astype(v_cache.dtype), cache_index, axis=1)
        new_cache = (k_cache, v_cache, cache_index + S)
        q_offset = cache_index
        k_exp = jax.lax.dynamic_slice_in_dim(
            _expand_kv(k_cache, n_rep), off, h_local, axis=2)
        v_exp = jax.lax.dynamic_slice_in_dim(
            _expand_kv(v_cache, n_rep), off, h_local, axis=2)
    else:
        q_offset = 0
        k_exp, v_exp = slice_expand_kv(k, v, h_local, n_rep, mi)

    interpret = attn_impl == "pallas_interpret"
    on_kernel = (attn_impl != "jnp" and causal and kv_cache is None
                 and paged_kv is None
                 and (mi.platform == "tpu" or interpret)
                 and kops.attention_blocks(S, hd) is not None)
    jax.monitoring.record_event(ATTENTION_EVENT,
                                path="kernel" if on_kernel else "chunked")
    if on_kernel:
        # the kernel's custom_vjp saves only q, k, v, the output and the
        # f32 row statistics; the layer's remat recomputes those
        out = kops.causal_attention_train(
            q, k_exp, v_exp, softmax_scale=1.0 / math.sqrt(hd),
            interpret=interpret)
    else:
        # inner remat: recompute attention internals in the backward from
        # (q, k, v), exactly like FlashAttention -- without this the
        # chunk-scan residuals (probs, partial sums) get stacked and saved
        attn_fn = jax.checkpoint(
            lambda q_, k_, v_: chunked_causal_attention(
                q_, k_, v_, q_offset=q_offset, causal=causal),
            policy=jax.checkpoint_policies.nothing_saveable)
        out = attn_fn(q, k_exp, v_exp)

    mask = local_head_mask(mi, padded_heads, cfg.num_heads)
    out = out * mask[None, None, :, None].astype(out.dtype)
    out = out.reshape(B, S, h_local * hd)
    y = project(out, wo, None, lora, "wo", lora_alpha)
    return psum_tp_act(y, mi), new_cache


def apply_rope_heads(x, positions, theta):
    from repro.models.layers import apply_rope
    return apply_rope(x, positions, theta)


# ---------------------------------------------------------------------------
# Decode attention over a sequence-sharded KV cache (long_500k).
# Flash-decoding adapted to the mesh: each 'data' shard holds S/data of the
# KV cache; partial (max, sumexp, weighted-V) stats are combined with
# collectives instead of a second kernel pass.
# ---------------------------------------------------------------------------

def seq_sharded_decode_attention(q, k_shard, v_shard, valid_len_local,
                                 mi: MeshInfo, seq_axis: str = "data"):
    """q: [B, 1, H, hd]; k_shard/v_shard: [B, S_local, KVH, hd] (this
    rank's slice of the cache); valid_len_local: [] number of valid
    positions in the local shard. Returns [B, 1, H, hd]."""
    B, _, H, hd = q.shape
    S_local = k_shard.shape[1]
    n_kv = k_shard.shape[2]
    n_rep = H // n_kv
    k_exp = _expand_kv(k_shard, n_rep).astype(jnp.float32)
    v_exp = _expand_kv(v_shard, n_rep).astype(jnp.float32)
    qf = q[:, 0].astype(jnp.float32)                       # [B,H,hd]
    s = jnp.einsum("bhd,bkhd->bhk", qf, k_exp) / math.sqrt(hd)
    pos = jnp.arange(S_local)
    s = jnp.where(pos[None, None, :] < valid_len_local, s, NEG_INF)
    m_local = jax.lax.stop_gradient(jnp.max(s, axis=-1))    # [B,H]
    m = jax.lax.pmax(m_local, seq_axis)
    p = jnp.exp(s - m[..., None])
    l_local = jnp.sum(p, axis=-1)
    acc_local = jnp.einsum("bhk,bkhd->bhd", p, v_exp)
    l = jax.lax.psum(l_local, seq_axis)
    acc = jax.lax.psum(acc_local, seq_axis)
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out[:, None].astype(q.dtype)                     # [B,1,H,hd]

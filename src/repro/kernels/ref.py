"""Pure-jnp oracles for every Pallas kernel. These are the ground truth
the kernels are validated against (tests sweep shapes/dtypes with
assert_allclose)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.quant import BLOCK, INV_QMAX, SCALE_EPS


def attention_ref(q, k, v, causal: bool = True, softmax_scale=None):
    """Naive full-materialization attention. q/k/v: [B, S, H, hd]."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    scale = softmax_scale or (1.0 / math.sqrt(hd))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(Sq)[:, None] + (Skv - Sq)
        kpos = jnp.arange(Skv)[None, :]
        mask = kpos <= qpos
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def rwkv6_ref(r, k, v, logw, u):
    """Sequential RWKV-6 WKV recurrence (exact). r/k/v/logw: [B,S,H,hd],
    u: [H,hd]. Returns ([B,S,H,hd], final state [B,H,hd,hd]).

    o_t = r_t @ (S + u*outer(k_t, v_t));  S <- diag(w_t) S + outer(k_t, v_t)
    """
    B, S, H, hd = r.shape
    rf, kf, vf = (x.astype(jnp.float32) for x in (r, k, v))
    wf = jnp.exp(logw.astype(jnp.float32))
    uf = u.astype(jnp.float32)

    def step(state, inp):
        rt, kt, vt, wt = inp
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        out = jnp.einsum("bhk,bhkv->bhv", rt, state + uf[None, :, :, None] * kv)
        state = wt[..., None] * state + kv
        return state, out

    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    sf, outs = jax.lax.scan(
        step, s0, (rf.swapaxes(0, 1), kf.swapaxes(0, 1),
                   vf.swapaxes(0, 1), wf.swapaxes(0, 1)))
    return outs.swapaxes(0, 1).astype(r.dtype), sf


def mamba_scan_ref(a, b, h0=None):
    """Sequential diagonal-SSM scan. a, b: [B, S, D, N] (decay, input);
    h_t = a_t * h_{t-1} + b_t. Returns (all states [B,S,D,N], h_last)."""
    B, S, D, N = a.shape
    h0 = jnp.zeros((B, D, N), jnp.float32) if h0 is None else h0

    def step(h, inp):
        at, bt = inp
        h = at * h + bt
        return h, h

    hl, hs = jax.lax.scan(
        step, h0, (a.astype(jnp.float32).swapaxes(0, 1),
                   b.astype(jnp.float32).swapaxes(0, 1)))
    return hs.swapaxes(0, 1), hl


def int8_quantize_blocks_ref(x):
    """Symmetric per-block quantization. x: [nb, BLOCK] float.
    Returns (q int8 [nb, BLOCK], scale f32 [nb, 1])."""
    blocks = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
                        * INV_QMAX, SCALE_EPS)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_dequantize_blocks_ref(q, s):
    """(q int8 [nb, BLOCK], s f32 [nb, 1]) -> f32 [nb, BLOCK]."""
    return q.astype(jnp.float32) * s


def int8_dequant_acc_ref(q, s):
    """Reduce-scatter inner loop oracle: fold the n dequantized source
    chunks sequentially (same order and f32 adds as the kernel's grid
    loop, so interpret-mode comparisons can be bit-exact).
    q: [n, nb, BLOCK] int8, s: [n, nb, 1] f32 -> f32 [nb, BLOCK]."""
    acc = jnp.zeros(q.shape[1:], jnp.float32)
    for i in range(q.shape[0]):
        acc = acc + q[i].astype(jnp.float32) * s[i]
    return acc


def matmul_chunk_ref(x, w, block_m: int = 128, block_n: int = 128):
    """Tile-loop mirror of collective_matmul.matmul_chunk: pad to the
    (block_m, block_n) grid, one f32-accumulated jnp.dot per tile with
    the contraction kept whole, rounded once to the output dtype, slice
    the pad back off. Interpret-mode Pallas executes exactly this
    per-tile dot, so comparisons can be bit-exact."""
    M, K = x.shape
    N = w.shape[1]
    pm, pn = (-M) % block_m, (-N) % block_n
    xp = jnp.pad(x, ((0, pm), (0, 0)))
    wp = jnp.pad(w, ((0, 0), (0, pn)))
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    rows = []
    for i in range(xp.shape[0] // block_m):
        tiles = [jnp.dot(xp[i * block_m:(i + 1) * block_m],
                         wp[:, j * block_n:(j + 1) * block_n],
                         preferred_element_type=jnp.float32
                         ).astype(out_dtype)
                 for j in range(wp.shape[1] // block_n)]
        rows.append(jnp.concatenate(tiles, axis=1))
    return jnp.concatenate(rows, axis=0)[:M, :N].astype(out_dtype)


def ag_matmul_ref(x, w_chunks):
    """Oracle for the fused all-gather->matmul ring: per-chunk matmuls
    written to disjoint column blocks in global (rank) order. x: [M, K];
    w_chunks: [n, K, Nc] (chunk j = rank j's shard). Chunk results are
    disjoint, so the ring's owner schedule is order-irrelevant here."""
    return jnp.concatenate([x @ w_chunks[j]
                            for j in range(w_chunks.shape[0])], axis=-1)


def matmul_rs_ref(a_chunks, b_chunks, rank: int):
    """Oracle for the fused matmul->reduce-scatter ring, for one rank.

    a_chunks: [n, J, M], b_chunks: [n, M, N] (per-rank local operands).
    Chunk ``rank`` is born on rank+1 and accumulates hop by hop (ranks
    rank+2, ..., rank-1, finally rank) -- mirror that exact left-to-
    right order so interpret-mode comparisons can be bit-exact."""
    n = a_chunks.shape[0]
    Nc = b_chunks.shape[2] // n
    acc = None
    for h in range(n):
        src = (rank + 1 + h) % n
        part = a_chunks[src] @ b_chunks[src][:, rank * Nc:(rank + 1) * Nc]
        acc = part if acc is None else acc + part
    return acc


def fused_bwd_dx_ref(g, w_chunks, rank: int):
    """Oracle for mode='both' dx: per-chunk contributions accumulated in
    ring order (owner = (rank + s) % n at step s). g: [M, N] cotangent;
    w_chunks: [n, K, Nc]. Returns [M, K]."""
    n, _, Nc = w_chunks.shape
    dx = None
    for s in range(n):
        owner = (rank + s) % n
        part = g[:, owner * Nc:(owner + 1) * Nc] @ w_chunks[owner].T
        dx = part if dx is None else dx + part
    return dx


def int8_quant_ref(x, block: int = BLOCK):
    """Blockwise symmetric int8 quantization oracle (flattens + pads)."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
                        * INV_QMAX, SCALE_EPS)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    deq = (q.astype(jnp.float32) * scale).reshape(-1)
    n = x.size
    return q, scale, deq[:n].reshape(x.shape)

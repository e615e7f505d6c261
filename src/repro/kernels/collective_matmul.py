"""Gather-fused collective matmul: consume stage-2 shards as they arrive.

The stage-2 (intra-pod / ICI) all-gather in ``core/fcdp.gather_stage2``
normally completes before the first consuming matmul starts. For
output-dim-sharded weights (w: [K, N] sharded along N over one intra
axis) the product decomposes into disjoint column blocks::

    x @ w_full = concat_j(x @ w_chunk_j)     # no K re-association

so each device multiplies its resident chunk immediately and ring-
``ppermute``s the remaining chunks behind the per-chunk matmuls -- the
transfer of chunk s+1 overlaps the matmul of chunk s, making the
stage-2 overlap a kernel-level property instead of a scan-level one.
Ring wire bytes equal the tiled all-gather's ((n-1)/n of the gathered
payload), so the swap is byte-neutral and the overlap credit is pure
win (see ``chunk_schedule`` and ``launch/roofline.py``).

Two duals live here:
  ring_ag_matmul:  all-gather -> matmul fused ring (forward path)
  ring_matmul_rs:  matmul -> reduce-scatter fused ring (weight-grad path)

Bit-exactness contract (asserted in tests/test_fused_matmul.py):
  * the forward equals ``x @ all_gather(w, tiled=True)`` bit-for-bit
    (column-concat identity; the contraction K is never split);
  * mode='ag_matmul' backward REPLAYS the exact unfused op sequence
    (all_gather + dot_general transposes + psum_scatter, via jax.vjp of
    the baseline expression), so gradients -- and therefore losses and
    params across steps -- are bit-identical to the unfused path;
  * mode='both' additionally ring-fuses the backward (dx accumulation +
    dw matmul-reduce-scatter). That re-associates the dx sum, so 'both'
    is bit-exact vs its own kernels/ref.py oracle, not vs the unfused
    gradient.

The per-chunk matmul is a Pallas kernel (impl='pallas'), tiled over
(block_m, block_n) with the contraction dim kept whole per program --
splitting K would re-associate the accumulation and break the contract.
Non-divisible shapes are padded up to the tile grid and sliced back
(same idiom as kernels/quant.py).
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


BLOCK_M = 128
BLOCK_N = 128


def _pad_dim(x, mult: int, axis: int):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _matmul_kernel(x_ref, w_ref, o_ref):
    # the TPU's matrix unit accumulates in f32 (Mosaic rejects a bf16
    # accumulator); round once to the output dtype
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def matmul_chunk(x, w, block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                 interpret: bool = False):
    """``x @ w`` as a Pallas blocked matmul. x: [M, K]; w: [K, N].

    The grid tiles M and N only; K stays whole per program, so every
    output element is one un-reassociated dot over the full contraction
    -- the property the bit-exactness contract rests on."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    xp = _pad_dim(x, block_m, 0)
    wp = _pad_dim(w, block_n, 1)
    Mp, Np = xp.shape[0], wp.shape[1]
    out = pl.pallas_call(
        _matmul_kernel,
        grid=(Mp // block_m, Np // block_n),
        in_specs=[pl.BlockSpec((block_m, K), lambda i, j: (i, 0)),
                  pl.BlockSpec((K, block_n), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        interpret=interpret,
    )(xp, wp)
    return out[:M, :N]


def _chunk_mm(x, w, impl: str, block_m: int, block_n: int, interpret: bool):
    """One per-chunk matmul on arbitrary-rank x ([..., K] @ [K, Nc])."""
    if impl == "jnp":
        return x @ w
    lead = x.shape[:-1]
    out = matmul_chunk(x.reshape(-1, x.shape[-1]), w, block_m, block_n,
                       interpret)
    return out.reshape(lead + (w.shape[1],))


def _ring_perm(n: int) -> List[Tuple[int, int]]:
    """After one hop rank i holds what rank i+1 held: chunk (i+s) % n
    after s hops, matching the ring's owner schedule."""
    return [((j + 1) % n, j) for j in range(n)]


def ring_ag_matmul(x, w_shard, axis_name: str, *, impl: str = "jnp",
                   block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                   interpret: bool = False):
    """Fused all-gather->matmul ring; call inside shard_map.

    x: [..., K] this rank's local activations. w_shard: [K, N/n] this
    rank's column chunk (global column order == rank order along
    ``axis_name``, exactly the tiled all-gather layout). Returns
    ``x @ w_full``: [..., N], bit-identical to gathering first.

    Each step issues the next chunk's ppermute BEFORE the current
    chunk's matmul so the transfer and the compute are concurrently
    ready in program order (XLA overlaps them); chunk results land in
    disjoint column slices of the output."""
    n = jax.lax.axis_size(axis_name)
    Nc = w_shard.shape[1]
    if n == 1:
        return _chunk_mm(x, w_shard, impl, block_m, block_n, interpret)
    idx = jax.lax.axis_index(axis_name)
    out_dtype = jnp.result_type(x.dtype, w_shard.dtype)
    out = jnp.zeros(x.shape[:-1] + (n * Nc,), out_dtype)
    perm = _ring_perm(n)
    chunk = w_shard
    for s in range(n):
        nxt = jax.lax.ppermute(chunk, axis_name, perm) if s < n - 1 else None
        owner = (idx + s) % n
        part = _chunk_mm(x, chunk, impl, block_m, block_n, interpret)
        start = (0,) * (out.ndim - 1) + (owner * Nc,)
        out = jax.lax.dynamic_update_slice(out, part.astype(out_dtype), start)
        chunk = nxt
    return out


def ring_matmul_rs(a, b, axis_name: str, *, impl: str = "jnp",
                   block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                   interpret: bool = False):
    """Fused matmul->reduce-scatter ring; call inside shard_map.

    a: [J, M] and b: [M, N] local operands; returns this rank's column
    chunk of ``sum_ranks(a @ b)``: [J, N/n] -- the fused form of
    ``psum_scatter(a @ b, axis_name, scatter_dimension=1, tiled=True)``.

    Chunk j's partial is born on rank j+1 and accumulates hop by hop
    around the ring (ranks j+2, ..., j-1, finally j), so each hop's
    transfer overlaps the receiver's partial matmul. The accumulation
    order is fixed by that schedule; kernels/ref.py mirrors it."""
    n = jax.lax.axis_size(axis_name)
    N = b.shape[1]
    assert N % n == 0, (b.shape, n)
    Nc = N // n
    if n == 1:
        return _chunk_mm(a, b, impl, block_m, block_n, interpret)
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]   # j sends to j+1
    buf = None
    for h in range(n):
        c = (idx + (n - 1 - h)) % n
        cols = jax.lax.dynamic_slice(b, (0, c * Nc), (b.shape[0], Nc))
        part = _chunk_mm(a, cols, impl, block_m, block_n, interpret)
        buf = part if buf is None else (
            jax.lax.ppermute(buf, axis_name, perm) + part)
    return buf


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def fused_matmul(x, w_shard, axis_name: str, mode: str = "ag_matmul",
                 impl: str = "jnp", block_m: int = BLOCK_M,
                 block_n: int = BLOCK_N, interpret: bool = False):
    """Differentiable gather-fused matmul (see module docstring).

    mode='ag_matmul': fused forward, bit-parity baseline-replay
    backward. mode='both': backward ring-fused too (dx ring + dw
    matmul-reduce-scatter; exact vs the ref.py oracle only)."""
    return ring_ag_matmul(x, w_shard, axis_name, impl=impl,
                          block_m=block_m, block_n=block_n,
                          interpret=interpret)


def _fused_fwd(x, w_shard, axis_name, mode, impl, block_m, block_n,
               interpret):
    y = fused_matmul(x, w_shard, axis_name, mode, impl, block_m, block_n,
                     interpret)
    return y, (x, w_shard)


def _fused_bwd(axis_name, mode, impl, block_m, block_n, interpret, res, g):
    x, w_shard = res
    if mode != "both":
        # bit-parity backward: replay the exact op sequence AD emits for
        # the unfused x @ all_gather(w) -- the gather, the two
        # dot_general transposes, and the psum_scatter -- so the
        # cotangents are bit-identical to the unfused path
        def baseline(x_, w_):
            w_full = jax.lax.all_gather(w_, axis_name, axis=1, tiled=True)
            return x_ @ w_full
        _, vjp = jax.vjp(baseline, x, w_shard)
        return tuple(vjp(g))
    # mode='both': ring-fused backward. dx accumulates per-chunk
    # contributions in ring order (re-associated); dw is the fused
    # matmul->reduce-scatter dual.
    n = jax.lax.axis_size(axis_name)
    K = x.shape[-1]
    Nc = w_shard.shape[1]
    x2 = x.reshape(-1, K)
    g2 = g.reshape(-1, g.shape[-1])
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)
    chunk = w_shard
    dx2 = jnp.zeros(x2.shape, jnp.result_type(g.dtype, w_shard.dtype))
    for s in range(n):
        nxt = jax.lax.ppermute(chunk, axis_name, perm) if s < n - 1 else None
        owner = (idx + s) % n
        g_cols = jax.lax.dynamic_slice(g2, (0, owner * Nc),
                                       (g2.shape[0], Nc))
        dx2 = dx2 + _chunk_mm(g_cols, chunk.T, impl, block_m, block_n,
                              interpret)
        chunk = nxt
    dw = ring_matmul_rs(x2.T, g2, axis_name, impl=impl, block_m=block_m,
                        block_n=block_n, interpret=interpret)
    return (dx2.reshape(x.shape).astype(x.dtype), dw.astype(w_shard.dtype))


fused_matmul.defvjp(_fused_fwd, _fused_bwd)


def chunk_schedule(m_tokens: int, k: int, n_cols_local: int, n_ranks: int,
                   dtype_bytes: float = 2.0) -> List[Tuple[float, float]]:
    """The ring's per-step (transfer_bytes, matmul_flops) schedule.

    Step s multiplies one [m, k] x [k, n_local] chunk while the next
    chunk's ppermute is in flight; the last step has no concurrent
    transfer. ``launch/roofline.py`` turns this into the fused overlap
    credit: sum over steps of min(transfer_time, matmul_time)."""
    chunk_bytes = float(k) * n_cols_local * dtype_bytes
    chunk_flops = 2.0 * m_tokens * k * n_cols_local
    return [(chunk_bytes if s < n_ranks - 1 else 0.0, chunk_flops)
            for s in range(n_ranks)]

"""jit'd dispatch wrappers for the Pallas kernels.

One ``impl`` keyword everywhere: 'jnp' (pure-jnp oracle), 'pallas'
(real lowering), or 'pallas_interpret' (CPU-validated interpret mode).
The legacy ``interpret=True`` boolean is kept as a back-compat shim --
it upgrades impl='pallas' to 'pallas_interpret'. Model code selects via
SystemConfig.attn_impl / quant_impl / fused_impl.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref

IMPLS = ("jnp", "pallas", "pallas_interpret")


def resolve_impl(impl: str, interpret: bool = False):
    """Normalize (impl, legacy interpret flag) -> (impl, interpret).

    'pallas_interpret' and interpret=True both mean interpret-mode
    Pallas; the returned impl is 'jnp' or 'pallas'."""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")
    if impl == "jnp":
        return "jnp", False
    return "pallas", interpret or impl == "pallas_interpret"


@functools.partial(jax.jit, static_argnames=("causal", "softmax_scale",
                                             "block_q", "block_k",
                                             "interpret", "impl"))
def flash_attention(q, k, v, causal: bool = True,
                    softmax_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False, impl: str = "pallas"):
    """q/k/v: [B, S, H, hd] (kv pre-expanded to H heads)."""
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "jnp":
        return kref.attention_ref(q, k, v, causal=causal,
                                  softmax_scale=softmax_scale)
    from repro.kernels.flash_attention import flash_attention_fwd
    return flash_attention_fwd(
        q, k, v, causal=causal, softmax_scale=softmax_scale,
        block_q=block_q, block_k=block_k, interpret=interpret)


# Tiles of JAX's bundled flash-attention kernel for causal training rows,
# from a sweep on a TPU v5e at head_dim 128 over rows of 4096 with 16 and
# 32 heads (one forward and backward: 3.49 and 7.76 ms, against 3.59 and
# 8.19 with every tile 512, 14.9 and 36.7 with every tile 128): 512 rows
# of q and of k/v per tile, except 1024 rows of k/v in the dK/dV kernel.
ATTN_TILE = 512
ATTN_DKV_TILE_K = 1024
ATTN_MIN_TILE = 128       # the kernel's lane width: smaller never tiles


def _tile(seq_len: int, cap: int) -> int:
    """The largest power-of-two tile from 128 to ``cap`` dividing
    ``seq_len`` (a multiple of 128)."""
    t = cap
    while seq_len % t:
        t //= 2
    return t


def attention_blocks(seq_len: int, head_dim: int):
    """The bundled kernel's block sizes for causal rows of ``seq_len``
    at ``head_dim``, or None where the kernel does not tile them: a
    head_dim or a row length that is not a multiple of 128."""
    if head_dim % ATTN_MIN_TILE or seq_len % ATTN_MIN_TILE:
        return None
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    b, bk = _tile(seq_len, ATTN_TILE), _tile(seq_len, ATTN_DKV_TILE_K)
    return BlockSizes(block_q=b, block_k_major=b, block_k=b, block_b=1,
                      block_q_major_dkv=b, block_k_major_dkv=bk,
                      block_k_dkv=bk, block_q_dkv=b, block_k_major_dq=b,
                      block_k_dq=b, block_q_dq=b)


def _vary_like(x, vma):
    """Type ``x`` as varying over the mesh axes ``vma`` (no data moves)."""
    missing = tuple(vma - jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


@contextlib.contextmanager
def _bundled_kernel(interpret: bool):
    """Around a call of a Pallas kernel bundled with JAX: its
    pallas_calls carry no varying-axes types, so the check is off (the
    caller types the results); ``interpret`` runs it in the TPU
    interpreter, whose callbacks ``jax.checkpoint`` cannot hold."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.compat import check_vma
    with check_vma(False), (pltpu.force_tpu_interpret_mode() if interpret
                            else contextlib.nullcontext()):
        yield


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_train(q, k, v, softmax_scale, blocks, interpret):
    from jax.experimental.pallas.ops.tpu.flash_attention import \
        flash_attention
    with _bundled_kernel(interpret):
        out = flash_attention(q, k, v, causal=True, sm_scale=softmax_scale,
                              block_sizes=blocks)
    return _vary_like(out, jax.typeof(q).vma)


# The bundled kernel is a custom_vjp of its own: these call its forward
# rule (residuals q, k, v, the output and the f32 row statistics l, m)
# and its backward rule (the dK/dV and dQ kernels) directly, so that
# results and residuals take q's varying axes, which k, v and the
# cotangent share (``causal_attention_train``).

def _flash_train_fwd(q, k, v, softmax_scale, blocks, interpret):
    from repro.compat import flash_attention_fwd
    vma = jax.typeof(q).vma
    with _bundled_kernel(interpret):
        out, res = flash_attention_fwd(
            q, k, v, ab=None, segment_ids=None, save_residuals=False,
            causal=True, sm_scale=softmax_scale, block_sizes=blocks,
            debug=False)
    return _vary_like(out, vma), jax.tree.map(
        lambda r: _vary_like(r, vma), res)


def _flash_train_bwd(softmax_scale, blocks, interpret, res, do):
    from repro.compat import flash_attention_bwd
    vma = jax.typeof(do).vma
    with _bundled_kernel(interpret):
        dq, dk, dv, _, _ = flash_attention_bwd(
            save_residuals=False, causal=True, sm_scale=softmax_scale,
            block_sizes=blocks, debug=False, residuals=res, do=do)
    return tuple(_vary_like(g, vma) for g in (dq, dk, dv))


_flash_train.defvjp(_flash_train_fwd, _flash_train_bwd)


def causal_attention_train(q, k, v, *, softmax_scale: float,
                           interpret: bool = False):
    """Causal self-attention on JAX's bundled Pallas flash-attention
    kernel, differentiable by its own backward kernels.

    q/k/v: [B, S, H, hd] (kv expanded to H heads), with S and hd tiled
    by ``attention_blocks``. The scores meet the scale in f32, the
    softmax statistics are f32 and the probabilities enter the PV
    product in v's dtype. Blocks above the diagonal are skipped, and the
    backward saves only q, k, v, the output and the per-row statistics.
    Works inside a ``shard_map`` with varying-axes checks on;
    ``interpret`` runs the kernels in the TPU interpreter (any backend,
    not under ``jax.checkpoint``). Not jit-wrapped: it traces inside
    the caller's ``shard_map`` body."""
    S, hd = q.shape[1], q.shape[3]
    blocks = attention_blocks(S, hd)
    if blocks is None:
        raise ValueError(f"rows of {S} at head_dim {hd} do not tile the "
                         f"flash-attention kernel")
    vma = frozenset().union(*(jax.typeof(x).vma for x in (q, k, v)))
    q, k, v = (_vary_like(x, vma).transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _flash_train(q, k, v, softmax_scale, blocks, interpret)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "impl"))
def wkv6(r, k, v, logw, u, chunk: int = 64, interpret: bool = False,
         impl: str = "pallas"):
    """RWKV-6 WKV. r/k/v/logw: [B,S,H,hd]; u: [H,hd]."""
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "jnp":
        return kref.rwkv6_ref(r, k, v, logw, u)
    from repro.kernels.rwkv6_scan import wkv6_chunked
    return wkv6_chunked(r, k, v, logw, u, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "impl"))
def int8_quantize_blocks(x, interpret: bool = False, impl: str = "pallas"):
    """Symmetric per-block int8 quantize. x: [nb, BLOCK] float.
    Returns (q int8 [nb, BLOCK], scale f32 [nb, 1])."""
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "jnp":
        return kref.int8_quantize_blocks_ref(x)
    from repro.kernels.quant import quantize_blocks
    return quantize_blocks(x, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "impl"))
def int8_dequantize_blocks(q, s, interpret: bool = False,
                           impl: str = "pallas"):
    """(q int8 [nb, BLOCK], s f32 [nb, 1]) -> f32 [nb, BLOCK]."""
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "jnp":
        return kref.int8_dequantize_blocks_ref(q, s)
    from repro.kernels.quant import dequantize_blocks
    return dequantize_blocks(q, s, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "impl"))
def int8_dequant_accumulate(q, s, interpret: bool = False,
                            impl: str = "pallas"):
    """Reduce-scatter inner loop: sequential dequant-accumulate of the
    n source chunks. q: [n, nb, BLOCK] int8, s: [n, nb, 1] f32."""
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "jnp":
        return kref.int8_dequant_acc_ref(q, s)
    from repro.kernels.quant import dequant_accumulate
    return dequant_accumulate(q, s, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "channel_block",
                                             "interpret", "impl"))
def ssm_scan(a, b, chunk: int = 128, channel_block: int = 512,
             interpret: bool = False, impl: str = "pallas"):
    """Diagonal SSM scan h_t = a_t h_{t-1} + b_t over [B,S,C]."""
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "jnp":
        B, S, C = a.shape
        hs, _ = kref.mamba_scan_ref(a.reshape(B, S, C, 1),
                                    b.reshape(B, S, C, 1))
        return hs.reshape(B, S, C)
    from repro.kernels.mamba_scan import mamba_scan
    return mamba_scan(a, b, chunk=chunk, channel_block=channel_block,
                      interpret=interpret)


def collective_ag_matmul(x, w_shard, axis_name: str, mode: str = "ag_matmul",
                         impl: str = "jnp", block_m: int = 128,
                         block_n: int = 128, interpret: bool = False):
    """Gather-fused collective matmul (kernels/collective_matmul.py):
    consumes the stage-2 column chunks as the ring delivers them.

    NOT jit-wrapped like the ops above: it carries named-axis
    collectives (ppermute / psum_scatter) and a custom_vjp, so it must
    trace directly inside the caller's shard_map body."""
    from repro.kernels.collective_matmul import fused_matmul
    impl, interpret = resolve_impl(impl, interpret)
    return fused_matmul(x, w_shard, axis_name, mode, impl, block_m,
                        block_n, interpret)

"""Beyond-paper optimization: int8 block-quantized TP activation
all-reduce.

The roofline breakdown (EXPERIMENTS.md §Roofline) shows the dominant ICI
term on dense train cells is NOT the ZeRO parameter traffic but the
Megatron-TP f/g-pair activation all-reduces (57 GB/chip on
qwen/train_4k). An all-reduce is reduce-scatter + all-gather; running
both hops in int8 (symmetric per-256-block scales) halves the bytes at
~0.4% relative error per tensor.

Forward-only compression: the backward of this psum is the standard
identity/pcast transpose (exact), so gradients see no additional
quantization beyond what the forward activations already carry.

The quantize/dequantize/accumulate hot loops are the shared codepath in
kernels/quant.py (jnp oracle or Pallas kernel, selected by `impl` --
see SystemConfig.quant_impl).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.compat import all_gather_invariant
from repro.core.grad_compress import _impl_kw
from repro.kernels import ops as kops
from repro.kernels.quant import BLOCK


def _int8_allreduce(x: jax.Array, axis_name: str,
                    impl: str = "jnp") -> jax.Array:
    """Quantized ring all-reduce: int8 RS (via all_to_all + local
    dequant-accumulate) followed by int8 invariant AG. Returns the
    (approximately) summed tensor, invarying over `axis_name`."""
    n = jax.lax.axis_size(axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    total = flat.shape[0]
    # pad so each of the n chunks is a whole number of quant blocks
    per = -(-total // (n * BLOCK)) * BLOCK
    pad = per * n - total
    if pad:
        flat = jnp.pad(flat, (0, pad))
    nb = per // BLOCK
    q, scale = kops.int8_quantize_blocks(
        flat.reshape(n * nb, BLOCK), **_impl_kw(impl))
    # reduce-scatter hop (int8): every rank receives all ranks' copy of
    # its own chunk, then runs the dequant-accumulate inner loop
    q_x = jax.lax.all_to_all(q.reshape(n, nb, BLOCK), axis_name,
                             split_axis=0, concat_axis=0,
                             tiled=True).reshape(n, nb, BLOCK)
    s_x = jax.lax.all_to_all(scale.reshape(n, nb, 1), axis_name,
                             split_axis=0, concat_axis=0,
                             tiled=True).reshape(n, nb, 1)
    own = kops.int8_dequant_accumulate(q_x, s_x, **_impl_kw(impl))
    # all-gather hop (int8) to rebuild the full summed tensor
    q2, s2 = kops.int8_quantize_blocks(own, **_impl_kw(impl))
    q_full = all_gather_invariant(q2, axis_name, axis=0, tiled=True)
    s_full = all_gather_invariant(s2, axis_name, axis=0, tiled=True)
    out = kops.int8_dequantize_blocks(
        q_full, s_full, **_impl_kw(impl)).reshape(-1)[:total]
    return out.reshape(shape).astype(dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def int8_psum(x, axis_name: str, impl: str = "jnp"):
    """Drop-in psum replacement with int8 transport. Exact-gradient:
    the transpose of a psum is the identity broadcast."""
    return _int8_allreduce(x, axis_name, impl)


def _fwd(x, axis_name, impl):
    return int8_psum(x, axis_name, impl), None


def _bwd(axis_name, impl, _, g):
    return (jax.lax.pcast(g, (axis_name,), to="varying"),)


int8_psum.defvjp(_fwd, _bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def int8_bwd_psum(x, axis_name: str, impl: str = "jnp"):
    """Identity whose BACKWARD all-reduce runs in int8.

    Column-parallel matmuls consume a TP-replicated input; autodiff's
    transpose inserts a full all-reduce on its cotangent (the Megatron
    g-bar). Wrapping the input here compresses that implicit reduction
    the same way int8_psum compresses the forward one."""
    return jax.lax.pcast(x, (axis_name,), to="varying")


def _bp_fwd(x, axis_name, impl):
    return int8_bwd_psum(x, axis_name, impl), None


def _bp_bwd(axis_name, impl, _, g):
    return (_int8_allreduce(g, axis_name, impl),)


int8_bwd_psum.defvjp(_bp_fwd, _bp_bwd)

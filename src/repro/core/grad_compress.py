"""Beyond-paper optimization: int8 block-quantized collectives over the
DCN ('pod') axis, after ZeRO++ (arXiv:2306.10209).

Two seams live here, both built on the shared per-256-block symmetric
quantization codepath in kernels/quant.py (jnp oracle or Pallas kernel,
selected by `impl`):

  * qgZ -- `compressed_stage1_gather`: the ordinary stage-1 all-gather
    whose *gradient* reduce-scatter transports int8 (half the DCN bytes
    of bf16). Forward stays exact.
  * qwZ -- `quantized_stage1_gather`: the stage-1 weight all-gather
    itself transports int8 blocks + fp32 scales and dequantizes on
    arrival (~2x fewer DCN bytes than bf16). Under FCDP the dequantized
    result is what gets host-cached, so the backward reuse stays free
    and full-precision.

`impl` is the config-level selector ('jnp' | 'pallas' |
'pallas_interpret'); kernels/ops.py owns the dispatch.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.quant import BLOCK, SCALE_EPS  # noqa: F401  (re-export)


def _impl_kw(impl: str) -> dict:
    """Map config-level quant_impl to kernels/ops.py dispatch kwargs."""
    if impl == "jnp":
        return {"impl": "jnp"}
    return {"impl": "pallas", "interpret": impl == "pallas_interpret"}


def _quantize(g: jax.Array, impl: str = "jnp") -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 blockwise quantization over the flattened tensor.
    Returns (q int8 [nb, BLOCK], scale f32 [nb, 1])."""
    flat = g.reshape(-1)
    pad = (-flat.shape[0]) % BLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return kops.int8_quantize_blocks(flat.reshape(-1, BLOCK).astype(
        jnp.float32), **_impl_kw(impl))


def int8_psum_scatter(g: jax.Array, axis_name: str, dim: int,
                      impl: str = "jnp") -> jax.Array:
    """Reduce-scatter over `axis_name` along `dim`, transported in int8.

    Each rank splits g into n chunks along dim, quantizes, all_to_all's
    the chunks so rank j receives every rank's chunk j, then runs the
    dequant-accumulate inner loop. Result: the local shard of the
    reduced tensor.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return g
    # move dim to front and split into n chunks
    g_moved = jnp.moveaxis(g, dim, 0)
    lead = g_moved.shape[0]
    assert lead % n == 0
    chunk_elems = (lead // n) * math.prod(g_moved.shape[1:])
    flat = g_moved.reshape(n, chunk_elems).astype(jnp.float32)
    pad = (-chunk_elems) % BLOCK
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    nb = flat.shape[1] // BLOCK                     # blocks per chunk
    q, scale = kops.int8_quantize_blocks(
        flat.reshape(n * nb, BLOCK), **_impl_kw(impl))
    q_x = jax.lax.all_to_all(q.reshape(n, nb, BLOCK), axis_name,
                             split_axis=0, concat_axis=0,
                             tiled=True).reshape(n, nb, BLOCK)
    s_x = jax.lax.all_to_all(scale.reshape(n, nb, 1), axis_name,
                             split_axis=0, concat_axis=0,
                             tiled=True).reshape(n, nb, 1)
    summed = kops.int8_dequant_accumulate(
        q_x, s_x, **_impl_kw(impl)).reshape(-1)     # reduce over sources
    chunk_shape = (lead // n,) + g_moved.shape[1:]
    out = summed[:chunk_elems].reshape(chunk_shape)
    return jnp.moveaxis(out, 0, dim).astype(g.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def compressed_stage1_gather(w, axis_name: str, dim: int,
                             impl: str = "jnp"):
    """all_gather over the pod axis whose *gradient* reduce-scatter is
    int8-compressed (qgZ)."""
    return jax.lax.all_gather(w, axis_name, axis=dim, tiled=True)


def _fwd(w, axis_name, dim, impl):
    return compressed_stage1_gather(w, axis_name, dim, impl), None


def _bwd(axis_name, dim, impl, _, g):
    return (int8_psum_scatter(g, axis_name, dim, impl),)


compressed_stage1_gather.defvjp(_fwd, _bwd)


def _quantized_gather_fwd(w, axis_name: str, dim: int, impl: str):
    """int8-transported stage-1 all-gather: quantize the local shard,
    gather blocks + scales over the pod axis, dequantize on arrival."""
    n = jax.lax.axis_size(axis_name)
    w_moved = jnp.moveaxis(w, dim, 0)
    elems = w_moved.size
    q, s = _quantize(w_moved, impl)
    q_all = jax.lax.all_gather(q, axis_name, axis=0, tiled=True)
    s_all = jax.lax.all_gather(s, axis_name, axis=0, tiled=True)
    vals = kops.int8_dequantize_blocks(q_all, s_all, **_impl_kw(impl))
    # each rank contributed ceil(elems/BLOCK) blocks; drop per-rank pad
    vals = vals.reshape(n, -1)[:, :elems]
    out = vals.reshape((n * w_moved.shape[0],) + w_moved.shape[1:])
    return jnp.moveaxis(out, 0, dim).astype(w.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def quantized_stage1_gather(w, axis_name: str, dim: int,
                            compress_bwd: bool = False, impl: str = "jnp"):
    """qwZ: stage-1 weight all-gather in int8 blocks + fp32 scales.

    The gradient reduce-scatter stays exact unless `compress_bwd`
    additionally routes it through the qgZ int8 path (both halves of
    the ZeRO++ DCN reduction, stacked)."""
    return _quantized_gather_fwd(w, axis_name, dim, impl)


def _qg_fwd(w, axis_name, dim, compress_bwd, impl):
    return quantized_stage1_gather(w, axis_name, dim, compress_bwd,
                                   impl), None


def _qg_bwd(axis_name, dim, compress_bwd, impl, _, g):
    if compress_bwd:
        return (int8_psum_scatter(g, axis_name, dim, impl),)
    return (jax.lax.psum_scatter(g, axis_name, scatter_dimension=dim,
                                 tiled=True),)


quantized_stage1_gather.defvjp(_qg_fwd, _qg_bwd)

"""Shared model-side infrastructure: mesh info carried into shard_map,
axis-aware collectives, head padding."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.launch.mesh import fsdp_axes_of


@dataclass(frozen=True)
class MeshInfo:
    """Static view of the mesh, closed over by code running inside shard_map."""
    axis_names: Tuple[str, ...]
    axis_sizes: Tuple[int, ...]
    # transport for the large TP activation all-reduces (sublayer outputs):
    # 'bf16' (exact) or 'int8' (block-quantized, ~half the ICI bytes)
    act_psum: str = "bf16"
    # quantize/dequantize codepath for the int8 transports
    # (SystemConfig.quant_impl): 'jnp' | 'pallas' | 'pallas_interpret'
    quant_impl: str = "jnp"
    # the devices' platform ('tpu', 'cpu', ...): which kernels may lower
    platform: str = "cpu"

    @classmethod
    def from_mesh(cls, mesh, act_psum: str = "bf16",
                  quant_impl: str = "jnp") -> "MeshInfo":
        return cls(tuple(mesh.axis_names),
                   tuple(mesh.shape[a] for a in mesh.axis_names),
                   act_psum, quant_impl, mesh.devices.flat[0].platform)

    def size(self, name: str) -> int:
        return self.axis_sizes[self.axis_names.index(name)] if name in self.axis_names else 1

    @property
    def tp(self) -> int:
        return self.size("model")

    @property
    def fsdp_axes(self) -> Tuple[str, ...]:
        return fsdp_axes_of(self.axis_names)

    @property
    def dp(self) -> int:
        return math.prod(self.size(a) for a in self.fsdp_axes)

    @property
    def has_pod(self) -> bool:
        return "pod" in self.axis_names

    @property
    def seq_axis(self) -> str:
        """Axis used for sequence sharding in long-context decode."""
        return "data"


def psum_tp(x, mi: MeshInfo):
    # applied even at tp degree 1: the collective is free but the VMA
    # type transition (varying -> invarying over 'model') is required
    return jax.lax.psum(x, "model")


def psum_tp_act(x, mi: MeshInfo):
    """TP reduction for the LARGE activation tensors (sublayer outputs).
    Honors mi.act_psum: int8 transport halves the dominant ICI term on
    dense train cells (see EXPERIMENTS.md SSPerf)."""
    if mi.act_psum == "int8" and mi.tp > 1:
        from repro.core.act_compress import int8_psum
        return int8_psum(x, "model", mi.quant_impl)
    return jax.lax.psum(x, "model")


def tp_region_in(x, mi: MeshInfo):
    """Mark the entry of a column-parallel (TP) region: under
    act_psum='int8' the implicit backward all-reduce on this tensor's
    cotangent runs in int8 (Megatron g-bar compression)."""
    if mi.act_psum == "int8" and mi.tp > 1:
        if "model" not in jax.typeof(x).vma:
            from repro.core.act_compress import int8_bwd_psum
            return int8_bwd_psum(x, "model", mi.quant_impl)
    return x


def pmax_tp(x, mi: MeshInfo):
    return jax.lax.pmax(x, "model")


def psum_dp(x, mi: MeshInfo):
    axes = mi.fsdp_axes
    return jax.lax.psum(x, axes) if axes else x


def tp_rank(mi: MeshInfo):
    return jax.lax.axis_index("model")


def pad_heads(n_heads: int, tp: int) -> int:
    return ((n_heads + tp - 1) // tp) * tp


def pad_vocab(v: int, tp: int) -> int:
    return ((v + tp - 1) // tp) * v if False else ((v + tp - 1) // tp) * tp


def pvary_like(x, ref):
    """Lift x's varying-mesh-axes (VMA) type to match ref's.

    Zero-initialized scan carries are invarying constants, while scan
    bodies produce device-varying values; under shard_map's VMA typing
    the carry init must be cast to the body's type. No-op outside
    shard_map (avals then carry no vma)."""
    missing = tuple(jax.typeof(ref).vma - jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def pvary_tree_like(tree, ref_tree):
    return jax.tree.map(pvary_like, tree, ref_tree)


def local_head_mask(mi: MeshInfo, padded_heads: int, real_heads: int):
    """[local_heads] bool mask; False for padding heads on the last TP ranks."""
    local = padded_heads // mi.tp
    start = tp_rank(mi) * local
    idx = start + jnp.arange(local)
    return idx < real_heads

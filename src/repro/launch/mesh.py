"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.

Mesh semantics:
  pod   - crosses DCN (slow inter-pod links). FCDP's "inter-node" axis.
  data  - intra-pod ICI; batch / ZeRO sharding. FCDP's "intra-node" axis.
  model - intra-pod ICI; tensor/expert parallelism.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """Arbitrary mesh with Auto axis types (smoke tests, elastic re-mesh).
    ``devices`` restricts the mesh to an explicit subset -- the elastic
    path passes the surviving devices so a shrunk mesh never spans chips
    the surviving shape does not cover."""
    kw = {} if devices is None else {"devices": tuple(devices)}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kw)


def make_device_mesh(pod: int = 1, data: int = 1, model: int = 1):
    """Mesh over exactly the visible devices (one chip, one host of
    four): pod x data x model must equal the device count. A pod axis
    of 1 is left out, as on a single-pod production mesh; a 2-wide pod
    axis on one host runs over ICI, not DCN."""
    n = len(jax.devices())
    if pod * data * model != n:
        raise ValueError(
            f"mesh pod={pod} x data={data} x model={model} = "
            f"{pod * data * model} devices, but {n} are visible")
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def make_smoke_mesh(n_devices: Optional[int] = None,
                    multi_pod: bool = False):
    """Tiny mesh over locally available devices for CPU smoke tests.

    multi_pod carves a 2-wide pod axis off the front (needs >= 8
    devices, e.g. XLA_FLAGS=--xla_force_host_platform_device_count=8)
    so the DCN-facing scheduler streams -- stage-1 prefetch, async grad
    reduce, the cross-step pipeline -- are exercisable in smoke runs.
    """
    n = n_devices or len(jax.devices())
    if multi_pod:
        if n < 8:
            # never fall through silently: the pod-less mesh would gate
            # every DCN stream off and the run would pass vacuously
            raise ValueError(
                f"multi_pod smoke mesh needs >= 8 devices, have {n}; "
                "set XLA_FLAGS=--xla_force_host_platform_device_count=8")
        model = math.gcd(n // 2, 2)
        return make_mesh((2, n // 2 // model, model),
                         ("pod", "data", "model"))
    model = math.gcd(n, 2)
    data = n // model
    return make_mesh((data, model), ("data", "model"))


def mesh_axes(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def fsdp_axes_of(axis_names: Sequence[str]) -> Tuple[str, ...]:
    """ZeRO-3 sharding axes (all non-model axes), tiled INTRA-major
    (pod last): the two-stage gather runs stage 1 (pod) first, then
    stage 2 (data), so storage must be data-major for the staged
    reconstruction to land blocks in true global order. With pod-major
    tiling each stage-2 result would be a consistent block permutation
    of the weight -- invisible while every leaf shares one strategy,
    but wrong the moment per-tensor mixed sharding contracts a
    two-stage-gathered leaf against a single-stage (mics/hier/frozen)
    one. The single source of the ordering invariant: both
    ``fsdp_axes(mesh)`` and ``MeshInfo.fsdp_axes`` delegate here."""
    return (tuple(a for a in axis_names if a not in ("model", "pod"))
            + tuple(a for a in axis_names if a == "pod"))


def fsdp_axes(mesh) -> Tuple[str, ...]:
    """Axes over which ZeRO-3 shards parameters (see fsdp_axes_of)."""
    return fsdp_axes_of(mesh.axis_names)


def inter_axis(mesh) -> Optional[str]:
    """The slow (DCN) axis, if present."""
    return "pod" if "pod" in mesh.axis_names else None


def intra_fsdp_axes(mesh) -> Tuple[str, ...]:
    """Fast (ICI) fsdp axes: what FCDP re-gathers over in the backward."""
    return tuple(a for a in mesh.axis_names if a not in ("model", "pod"))


def dp_degree(mesh) -> int:
    return math.prod(mesh.shape[a] for a in fsdp_axes(mesh))


def tp_degree(mesh) -> int:
    return mesh.shape.get("model", 1)

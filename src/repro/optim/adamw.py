"""AdamW with ZeRO-sharded states and mixed-precision master weights.

Optimizer state leaves carry exactly the parameter's storage sharding, so
updates are purely local (ZeRO-3: each device updates only its shard).
State dtypes are configurable (fp32 default; bf16 m/v for HBM-tight
configs such as kimi-k2 at 512 chips, see EXPERIMENTS.md).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import OptimizerConfig, SystemConfig


def lr_at_step(cfg: OptimizerConfig, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "constant":
        decay = 1.0
    elif cfg.schedule == "linear":
        t = jnp.clip((step - cfg.warmup_steps)
                     / max(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
        decay = 1.0 - t
    else:  # cosine
        t = jnp.clip((step - cfg.warmup_steps)
                     / max(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
        decay = 0.5 * (1 + jnp.cos(jnp.pi * t))
    return cfg.lr * warm * decay


def init_opt_state(train_params: List[jax.Array], sys: SystemConfig):
    """m, v (opt dtype) + fp32 master copies, all sharded like params."""
    od = jnp.dtype(sys.opt_state_dtype)
    md = jnp.dtype(sys.master_dtype)
    return {
        "m": [jnp.zeros(p.shape, od) for p in train_params],
        "v": [jnp.zeros(p.shape, od) for p in train_params],
        "master": [p.astype(md) for p in train_params],
        "step": jnp.zeros((), jnp.int32),
    }


def clip_by_global_norm(grads: List[jax.Array], rep_factors: Sequence[float],
                        max_norm: float, dp_axes, tp_present: bool = True):
    """Global-norm clip aware of sharding: each leaf's local sum-of-squares
    is divided by its replication factor, then psum'd over every mesh axis
    so each element counts exactly once. The psum always includes 'model'
    (even at tp degree 1) for VMA type correctness."""
    local = jnp.float32(0)
    for g, rep in zip(grads, rep_factors):
        local = local + jnp.sum(jnp.square(g.astype(jnp.float32))) / rep
    axes = tuple(dp_axes) + ("model",)
    if axes:
        # lift to varying over every axis (identical copies psum-corrected
        # by the replication factors above), then reduce over all
        have = jax.typeof(local).vma
        missing = tuple(a for a in axes if a not in have)
        if missing:
            local = jax.lax.pcast(local, missing, to="varying")
        total = jax.lax.psum(local, axes)
    else:
        total = local
    gnorm = jnp.sqrt(total)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-12))
    return [g * scale.astype(g.dtype) for g in grads], gnorm


def adamw_update(grads: List[jax.Array], opt_state: Dict[str, Any],
                 opt_cfg: OptimizerConfig, sys: SystemConfig,
                 wd_mask: Optional[Sequence[bool]] = None):
    """Returns (new_params_bf16, new_opt_state). Purely elementwise."""
    step = opt_state["step"] + 1
    lr = lr_at_step(opt_cfg, step)
    b1, b2, eps = opt_cfg.b1, opt_cfg.b2, opt_cfg.eps
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    od = jnp.dtype(sys.opt_state_dtype)
    pd = jnp.dtype(sys.param_dtype)
    new_m, new_v, new_master, new_params = [], [], [], []
    for i, (g, m, v, master) in enumerate(zip(
            grads, opt_state["m"], opt_state["v"], opt_state["master"])):
        gf = g.astype(jnp.float32)
        mf = m.astype(jnp.float32) * b1 + gf * (1 - b1)
        vf = v.astype(jnp.float32) * b2 + jnp.square(gf) * (1 - b2)
        upd = (mf / bc1) / (jnp.sqrt(vf / bc2) + eps)
        wd = opt_cfg.weight_decay if (wd_mask is None or wd_mask[i]) else 0.0
        mastf = master.astype(jnp.float32)
        mastf = mastf - lr * (upd + wd * mastf)
        new_m.append(mf.astype(od))
        new_v.append(vf.astype(od))
        new_master.append(mastf.astype(jnp.dtype(sys.master_dtype)))
        new_params.append(mastf.astype(pd))
    return new_params, {"m": new_m, "v": new_v, "master": new_master,
                        "step": step}

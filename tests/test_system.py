"""End-to-end behaviour tests for the FCDP system.

Covers: numerical equivalence of fcdp/zeropp/mics against the zero3
baseline (the paper's correctness claim -- caching must not change
math), comm-schedule structure (backward re-gather axes per mode),
PEFT classification, and training convergence per family.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (MambaConfig, ModelConfig, MoEConfig,
                                OptimizerConfig, RWKVConfig, RunConfig,
                                ShapeCell, SystemConfig)
from repro.core.engine import StepBundle
from repro.optim.adamw import init_opt_state

DENSE = ModelConfig(name="t-dense", family="dense", num_layers=2, d_model=64,
                    num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                    qkv_bias=True)
CELL = ShapeCell("t", "train", 64, 8)


def make_bundle(mesh, cfg=DENSE, mode="fcdp", cell=CELL, **sys_kw):
    sysd = dict(mode=mode, min_shard_size=8)
    sysd.update(sys_kw)
    run = RunConfig(model=cfg, shape=cell, system=SystemConfig(**sysd),
                    optimizer=OptimizerConfig(total_steps=8, warmup_steps=2,
                                              lr=1e-3))
    return StepBundle(run, mesh)


def make_batch(cfg, cell, seed=0):
    rng = np.random.default_rng(seed)
    b = {"ids": jnp.asarray(
            rng.integers(1, cfg.vocab_size, (cell.global_batch, cell.seq_len)),
            jnp.int32),
         "labels": jnp.asarray(
            rng.integers(1, cfg.vocab_size, (cell.global_batch, cell.seq_len)),
            jnp.int32)}
    b["mask"] = jnp.ones_like(b["labels"], bool)
    if cfg.num_encoder_layers > 0:
        b["enc_embeds"] = jnp.asarray(
            rng.standard_normal((cell.global_batch,
                                 max(cell.seq_len // 4, 8), cfg.d_model)),
            jnp.bfloat16)
    return b


def run_steps(bundle, n=2, seed=0):
    params = bundle.init_all_params(seed=0)
    tp, fp = bundle.split(params)
    opt = jax.jit(functools.partial(
        init_opt_state, sys=bundle.run.system))(tp)
    step = bundle.make_train_step()
    batch = make_batch(bundle.run.model, bundle.run.shape, seed)
    ms = []
    for _ in range(n):
        tp, opt, m = step(tp, fp, opt, batch)
        ms.append({k: float(v) for k, v in m.items()})
    return tp, ms


# ---------------------------------------------------------------------------
# The paper's correctness invariant: the caching schedule must not change
# the math. All four systems produce identical losses and gradients.
# ---------------------------------------------------------------------------

def test_modes_numerically_equivalent(mesh3):
    """One training step must produce the same loss, grad norm, and
    updated parameters in every mode (caching cannot change the math).
    Tolerances absorb f32 collective reduction-order nondeterminism."""
    out = {}
    for mode in ("zero3", "zeropp", "fcdp", "mics"):
        tp, ms = run_steps(make_bundle(mesh3, mode=mode), n=1)
        out[mode] = (ms[0]["loss"], ms[0]["grad_norm"],
                     [np.asarray(x, np.float32) for x in tp])
    base_loss, base_gnorm, base_params = out["zero3"]
    for mode in ("zeropp", "fcdp", "mics"):
        loss, gnorm, params = out[mode]
        np.testing.assert_allclose(loss, base_loss, rtol=1e-4,
                                   err_msg=f"{mode} loss != zero3")
        np.testing.assert_allclose(gnorm, base_gnorm, rtol=1e-3)
        for a, b in zip(base_params, params):
            np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3,
                                       err_msg=f"{mode} params != zero3")


def test_loss_decreases_all_families(mesh3):
    cfgs = {
        "dense": DENSE,
        "moe": ModelConfig(name="t-moe", family="moe", num_layers=2,
                           d_model=64, num_heads=4, num_kv_heads=2, d_ff=64,
                           vocab_size=256,
                           moe=MoEConfig(num_experts=4, top_k=2,
                                         d_ff_expert=64)),
        "ssm": ModelConfig(name="t-rwkv", family="ssm", num_layers=2,
                           d_model=64, num_heads=0, num_kv_heads=0, d_ff=128,
                           vocab_size=256,
                           rwkv=RWKVConfig(head_dim=16, decay_lora=8)),
        "hybrid": ModelConfig(
            name="t-jamba", family="hybrid", num_layers=4, d_model=64,
            num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
            mamba=MambaConfig(d_state=8, dt_rank=8),
            moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=128,
                          moe_period=2, moe_offset=1),
            hybrid_period=2, hybrid_attn_positions=(0,)),
        "encdec": ModelConfig(
            name="t-encdec", family="encdec", num_layers=2, d_model=64,
            num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=256,
            num_encoder_layers=2, act="gelu", frontend="audio_frames"),
    }
    for fam, cfg in cfgs.items():
        _, ms = run_steps(make_bundle(mesh3, cfg=cfg), n=4)
        losses = [m["loss"] for m in ms]
        assert all(np.isfinite(losses)), f"{fam}: non-finite loss"
        assert losses[-1] < losses[0], f"{fam}: loss not decreasing {losses}"


# ---------------------------------------------------------------------------
# Comm schedule structure: the jaxpr must contain exactly the collective
# pattern Table VII is built on.
# ---------------------------------------------------------------------------

def _collect(bundle):
    from repro.launch.roofline import collect_collectives
    step = bundle.make_train_step()
    closed = step.trace(*bundle.train_input_sds()).jaxpr
    sizes = {a: bundle.mi.size(a) for a in bundle.mi.axis_names}
    return collect_collectives(closed, sizes)


def test_fcdp_halves_backward_pod_allgather(mesh3):
    z3 = _collect(make_bundle(mesh3, mode="zero3"))
    fc = _collect(make_bundle(mesh3, mode="fcdp"))
    # fcdp eliminates the backward pod-stage all-gather: pod-axis AG bytes
    # must drop by ~half (fwd-only), reduce-scatter unchanged.
    z3_ag = z3.by_op.get("all_gather", 0)
    fc_ag = fc.by_op.get("all_gather", 0)
    assert fc.dcn_bytes < z3.dcn_bytes * 0.8, (fc.dcn_bytes, z3.dcn_bytes)
    assert fc_ag < z3_ag
    np.testing.assert_allclose(fc.by_op.get("psum_scatter", 0),
                               z3.by_op.get("psum_scatter", 0), rtol=1e-6)


def test_mics_has_zero_dcn_allgather(mesh3):
    mi = _collect(make_bundle(mesh3, mode="mics"))
    # MiCS shards within the pod: all parameter all-gathers are ICI-only;
    # only gradient reduction (psum) crosses pods.
    assert mi.by_op.get("all_gather", 0) > 0
    assert mi.by_op_axis.get("all_gather/pod", 0) == 0
    assert mi.by_op_axis.get("psum_scatter/pod", 0) == 0
    assert mi.by_op_axis.get("psum/pod", 0) > 0   # grad all-reduce


def test_peft_eliminates_dcn_traffic(mesh3):
    """FCDP-Comm: frozen weights never cross DCN -- the pod-axis
    all-gather volume must collapse to the (tiny) LoRA adapters. At this
    toy scale replicated-bias gradient psums keep total DCN non-zero,
    so the assertion targets the all-gather/reduce-scatter components
    the paper's Table VII measures."""
    full = _collect(make_bundle(mesh3, mode="fcdp"))
    peft = _collect(make_bundle(mesh3, mode="fcdp", peft=True))
    full_ag = full.by_op_axis.get("all_gather/pod", 0)
    peft_ag = peft.by_op_axis.get("all_gather/pod", 0)
    assert peft_ag < full_ag * 0.12, (peft_ag, full_ag)
    full_rs = full.by_op_axis.get("psum_scatter/pod", 0)
    peft_rs = peft.by_op_axis.get("psum_scatter/pod", 0)
    assert peft_rs < full_rs * 0.12, (peft_rs, full_rs)
    assert peft.dcn_bytes < full.dcn_bytes * 0.25


def test_peft_classification(mesh3):
    b = make_bundle(mesh3, mode="fcdp", peft=True)
    n_train = len(b.train_idx)
    n_frozen = len(b.frozen_idx)
    assert n_train > 0 and n_frozen > 0
    # trainable = lora adapters only
    for i in b.train_idx:
        assert "_lora_" in b.def_leaves[i].label
    # trainable params are a small fraction
    train_sz = sum(b.def_leaves[i].size() for i in b.train_idx)
    total_sz = sum(d.size() for d in b.def_leaves)
    assert train_sz / total_sz < 0.2


def test_peft_training_updates_only_adapters(mesh3):
    b = make_bundle(mesh3, mode="fcdp", peft=True)
    params = b.init_all_params(seed=0)
    tp0, fp = b.split(params)
    # snapshot before the step: inputs are donated
    tp0_np = [np.asarray(x, np.float32) for x in tp0]
    opt = jax.jit(functools.partial(init_opt_state, sys=b.run.system))(tp0)
    step = b.make_train_step()
    batch = make_batch(b.run.model, b.run.shape)
    tp1, opt, m = step(tp0, fp, opt, batch)
    assert np.isfinite(m["loss"])
    changed = any(
        not np.allclose(a, np.asarray(bb, np.float32))
        for a, bb in zip(tp0_np, tp1))
    assert changed, "lora adapters did not update"


# ---------------------------------------------------------------------------
# Gradient correctness vs single-device reference (the sharded system
# computes the same gradients as unsharded jax).
# ---------------------------------------------------------------------------

def test_grads_match_unsharded_reference(mesh2):
    # tiny single-layer dense model, fcdp mode, compare loss trajectory
    cfg = ModelConfig(name="t-ref", family="dense", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=64)
    cell = ShapeCell("t", "train", 32, 4)
    b = make_bundle(mesh2, cfg=cfg, cell=cell, mode="fcdp")
    _, ms = run_steps(b, n=3)
    losses = [m["loss"] for m in ms]
    assert losses[-1] < losses[0]
    # grad norm finite and stable
    assert all(0 < m["grad_norm"] < 1e4 for m in ms)


def test_grad_accumulation_matches_full_batch(mesh3):
    cfg = DENSE
    cell = ShapeCell("t", "train", 64, 8)
    run_full = RunConfig(model=cfg, shape=cell,
                         system=SystemConfig(mode="fcdp", min_shard_size=8),
                         optimizer=OptimizerConfig(lr=1e-3, total_steps=8,
                                                   warmup_steps=2))
    from repro.launch.mesh import make_mesh
    b_full = StepBundle(run_full, mesh3)
    b_acc = StepBundle(run_full.replace(microbatch=2), mesh3)
    batch = make_batch(cfg, cell)
    out = {}
    for name, b in (("full", b_full), ("acc", b_acc)):
        params = b.init_all_params(seed=0)
        tp, fp = b.split(params)
        opt = jax.jit(functools.partial(init_opt_state, sys=b.run.system))(tp)
        tp, opt, m = b.make_train_step()(tp, fp, opt, batch)
        out[name] = [np.asarray(x, np.float32) for x in tp]
    for a, c in zip(out["full"], out["acc"]):
        np.testing.assert_allclose(a, c, rtol=5e-2, atol=5e-3)


def test_device_mesh_matches_device_count():
    """A run without --smoke lays its mesh over exactly the visible
    devices; a pod axis of 1 is left out, a wrong product raises."""
    from repro.launch.mesh import make_device_mesh
    m = make_device_mesh(2, 2, 2)
    assert m.axis_names == ("pod", "data", "model")
    assert dict(m.shape) == {"pod": 2, "data": 2, "model": 2}
    m = make_device_mesh(1, 4, 2)
    assert m.axis_names == ("data", "model")
    with pytest.raises(ValueError, match="visible"):
        make_device_mesh(1, 1, 1)


def test_train_launcher_published_config_path(monkeypatch):
    """The non-smoke launcher path: mesh from --pod/--data/--model,
    --batch/--seq-len honoured, --layers cuts depth only, no checkpoint
    dir means no restarts, and the optimizer state starts in the layout
    the step returns (so step 1 reuses step 0's compile)."""
    import argparse
    import dataclasses

    from repro.configs.qwen2_5_3b import SMOKE
    from repro.launch import train
    monkeypatch.setattr(train, "get_config",
                        lambda arch: dataclasses.replace(SMOKE, num_layers=4))
    st = train.main(["--arch", "qwen2.5-3b", "--pod", "2", "--data", "2",
                     "--model", "2", "--layers", "2", "--batch", "8",
                     "--seq-len", "64", "--steps", "2", "--ckpt-dir", ""])
    assert st.run.model.num_layers == 2
    assert st.run.model.d_model == SMOKE.d_model
    assert (st.run.shape.global_batch, st.run.shape.seq_len) == (8, 64)
    assert dict(st.mesh.shape) == {"pod": 2, "data": 2, "model": 2}
    assert st.result["restarts"] == 0
    assert all(np.isfinite(r["loss"]) for r in st.metrics_log)
    want = st.bundle.state_shardings()["opt"]
    for x, sh in zip(st.opt["m"], want["m"]):
        assert x.sharding.is_equivalent_to(sh, x.ndim)
    deeper = argparse.Namespace(smoke=False, arch="qwen2.5-3b", pod=2,
                                data=2, model=2, cell="train_4k",
                                seq_len=64, batch=8, layers=5)
    with pytest.raises(ValueError, match="has 4 layers"):
        train.build(deeper)

"""Substrate tests: checkpoint roundtrip + elastic resharding, data
pipeline determinism, fault-tolerance driver, gradient compression,
partition invariants (hypothesis property tests)."""
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

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover
    HAVE_HYP = False

DENSE = ModelConfig(name="t-dense", family="dense", num_layers=2, d_model=64,
                    num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256)
CELL = ShapeCell("t", "train", 64, 8)


def _bundle(mesh, **sys_kw):
    sysd = dict(mode="fcdp", min_shard_size=8)
    sysd.update(sys_kw)
    run = RunConfig(model=DENSE, shape=CELL, system=SystemConfig(**sysd),
                    optimizer=OptimizerConfig(total_steps=8, warmup_steps=2))
    return StepBundle(run, mesh)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path, mesh3):
    from repro.checkpoint.checkpointer import Checkpointer
    b = _bundle(mesh3)
    params = b.init_all_params(seed=0)
    tp, fp = b.split(params)
    ck = Checkpointer(str(tmp_path), keep=2)
    ck.save(7, {"params": tp}, blocking=True)
    assert ck.latest_step() == 7
    restored = ck.restore(7, {"params": tp})
    for a, c in zip(tp, restored["params"]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(c, np.float32))


def test_checkpoint_gc_and_async(tmp_path, mesh3):
    from repro.checkpoint.checkpointer import Checkpointer
    b = _bundle(mesh3)
    tp, _ = b.split(b.init_all_params(seed=0))
    ck = Checkpointer(str(tmp_path), keep=2)
    for step in (1, 2, 3):
        ck.save(step, {"params": tp}, blocking=False)
    ck.wait()
    assert ck.all_steps() == [2, 3]


def test_elastic_reshard_across_meshes(tmp_path, mesh3, mesh2):
    """A checkpoint written on the 3-axis (multi-pod) mesh restores onto
    the 2-axis mesh with identical values -- the pod-loss recovery path."""
    from repro.checkpoint.checkpointer import Checkpointer
    from jax.sharding import NamedSharding
    b3 = _bundle(mesh3)
    tp3, _ = b3.split(b3.init_all_params(seed=0))
    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"params": tp3}, blocking=True)

    b2 = _bundle(mesh2)
    shardings = {"params": [NamedSharding(b2.mesh, b2.leaf_specs[i])
                            for i in b2.train_idx]}
    restored = ck.restore(1, {"params": tp3}, shardings=shardings)
    for a, c in zip(tp3, restored["params"]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(c, np.float32))
    # and the restored params actually run a step on the new mesh
    fp2: list = []
    opt = jax.jit(functools.partial(
        init_opt_state, sys=b2.run.system))(restored["params"])
    batch = {"ids": jnp.ones((8, 64), jnp.int32),
             "labels": jnp.ones((8, 64), jnp.int32),
             "mask": jnp.ones((8, 64), bool)}
    tp_new, opt, m = b2.make_train_step()(restored["params"], fp2, opt, batch)
    assert np.isfinite(float(m["loss"]))


def test_surviving_mesh_shapes():
    from repro.runtime.elastic import surviving_mesh_shape
    assert surviving_mesh_shape(512, 16) == ((2, 16, 16),
                                             ("pod", "data", "model"))
    assert surviving_mesh_shape(256, 16) == ((16, 16), ("data", "model"))
    assert surviving_mesh_shape(128, 16) == ((8, 16), ("data", "model"))
    assert surviving_mesh_shape(8, 2) == ((4, 2), ("data", "model"))


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_determinism():
    from repro.data.pipeline import DataConfig, SyntheticPackedLM
    ds = SyntheticPackedLM(DENSE, CELL, DataConfig(seed=3))
    b1 = ds.batch_np(step=5)
    b2 = ds.batch_np(step=5)
    np.testing.assert_array_equal(b1["ids"], b2["ids"])
    b3 = ds.batch_np(step=6)
    assert not np.array_equal(b1["ids"], b3["ids"])
    assert b1["ids"].shape == (CELL.global_batch, CELL.seq_len)
    assert (b1["ids"] < DENSE.vocab_size).all()
    assert b1["mask"].dtype == np.bool_


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_restart_driver_recovers_from_failures():
    from repro.runtime.fault_tolerance import (FailureInjector,
                                               StragglerMonitor,
                                               run_with_restarts)
    state = {"x": 0.0, "ckpt": (0, 0.0)}
    inj = FailureInjector(fail_at_steps=(3, 7))

    def step_fn(step):
        inj.maybe_fail(step)
        state["x"] += 1.0

    def save(step):
        state["ckpt"] = (step, state["x"])

    def restore():
        step, x = state["ckpt"]
        state["x"] = x
        return step

    mon = StragglerMonitor(min_samples=2)
    res = run_with_restarts(10, step_fn, save, restore, checkpoint_every=2,
                            monitor=mon)
    assert res["final_step"] == 10
    assert res["restarts"] == 2
    assert state["x"] == 10.0      # no lost or double-applied steps


def test_straggler_monitor_flags_outlier():
    from repro.runtime.fault_tolerance import StragglerMonitor
    mon = StragglerMonitor(min_samples=5, z_threshold=3.0)
    for _ in range(20):
        mon.record(0.1 + np.random.default_rng(1).normal(0, 0.001))
    assert mon.record(5.0) is True
    assert mon.summary()["n_flagged"] == 1


def test_heartbeat_detects_hang():
    import time
    from repro.runtime.fault_tolerance import HeartbeatMonitor
    hb = HeartbeatMonitor(timeout_s=0.2).start()
    hb.beat()
    time.sleep(0.5)
    assert hb.hung
    hb.stop()


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_int8_pod_grad_compression_close_to_exact(mesh3):
    """Training with int8 DCN gradient compression stays close to the
    uncompressed run for one step."""
    outs = {}
    for gc in ("none", "int8_pod"):
        b = _bundle(mesh3, grad_compress=gc)
        params = b.init_all_params(seed=0)
        tp, fp = b.split(params)
        opt = jax.jit(functools.partial(
            init_opt_state, sys=b.run.system))(tp)
        batch = {"ids": jnp.ones((8, 64), jnp.int32) * 3,
                 "labels": jnp.ones((8, 64), jnp.int32) * 5,
                 "mask": jnp.ones((8, 64), bool)}
        tp1, opt, m = b.make_train_step()(tp, fp, opt, batch)
        outs[gc] = (float(m["loss"]), float(m["grad_norm"]))
    l0, g0 = outs["none"]
    l1, g1 = outs["int8_pod"]
    assert abs(l0 - l1) < 1e-4          # fwd identical
    assert abs(g0 - g1) / g0 < 0.05     # int8 grads within 5%


def test_int8_activation_allreduce_training_quality(mesh3):
    """int8 TP activation all-reduce (fwd f-pair + bwd g-bar): training
    loss must track the exact bf16 run closely (the §Perf 2x iteration)."""
    outs = {}
    batch = {"ids": jnp.ones((8, 64), jnp.int32) * 3,
             "labels": jnp.ones((8, 64), jnp.int32) * 5,
             "mask": jnp.ones((8, 64), bool)}
    for ap in ("bf16", "int8"):
        b = _bundle(mesh3, act_psum=ap)
        params = b.init_all_params(seed=0)
        tp, fp = b.split(params)
        opt = jax.jit(functools.partial(
            init_opt_state, sys=b.run.system))(tp)
        step = b.make_train_step()
        losses = []
        for _ in range(3):
            tp, opt, m = step(tp, fp, opt, batch)
            losses.append(float(m["loss"]))
        outs[ap] = losses
    # per-step relative tracking: blockwise-quant noise compounds over
    # steps (and backend reduction order shifts it), so bound the
    # relative drift rather than an absolute gap
    for a, c in zip(outs["bf16"], outs["int8"]):
        assert abs(a - c) / a < 0.08, (outs["bf16"], outs["int8"])
    assert outs["int8"][-1] < outs["int8"][0], outs["int8"]


def test_int8_allreduce_unit(mesh3, rng):
    """int8_psum matches exact psum within blockwise-quant error."""
    from jax.sharding import PartitionSpec as P
    from repro.core.act_compress import int8_psum

    def body(x):
        exact = jax.lax.psum(x, "model")
        approx = int8_psum(x, "model")
        return exact, approx

    x = jnp.asarray(rng.normal(0, 1, (8, 64, 64)), jnp.float32)
    fn = jax.shard_map(body, mesh=mesh3, in_specs=(P("model"),),
                       out_specs=(P("model"), P("model")))
    exact, approx = fn(x)
    e, a = np.asarray(exact), np.asarray(approx)
    rel = np.abs(e - a) / (np.abs(e).max() + 1e-9)
    assert rel.max() < 0.02, rel.max()


# ---------------------------------------------------------------------------
# partition invariants (hypothesis)
# ---------------------------------------------------------------------------

if HAVE_HYP:
    from repro.core.partition import ParamDef, storage_spec
    from repro.core.fcdp import make_gather_plan
    from repro.launch.mesh import make_mesh

    @given(st.integers(1, 8), st.integers(1, 8), st.booleans(),
           st.sampled_from(["zero3", "zeropp", "fcdp", "mics"]))
    @settings(max_examples=40, deadline=None)
    def test_partition_gather_consistency(mult_a, mult_b, frozen, mode):
        """Invariant: the gather plan reconstructs exactly the dims the
        storage spec sharded -- for every (shape x mode x frozen) combo."""
        import jax as _jax
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = (4 * mult_a, 2 * mult_b)
        d = ParamDef(shape, ("fsdp", "tp"), frozen=frozen)
        spec = storage_spec(d, mesh, mode)
        plan = make_gather_plan(d, mesh, mode)
        fsdp_entry = spec[0]
        if plan.is_gathered:
            got = set(plan.inter_axes) | set(plan.intra_axes)
            want = set(fsdp_entry if isinstance(fsdp_entry, tuple)
                       else (fsdp_entry,))
            assert got == want, (spec, plan)
            # cache boundary: stage-1 iff a DCN axis exists
            assert plan.cache_after == (1 if "pod" in got else 2)
        else:
            assert fsdp_entry is None

    @given(st.integers(1, 6), st.integers(1, 6), st.integers(1, 4))
    @settings(max_examples=30, deadline=None)
    def test_replication_factor_invariant(a, b, c):
        """sum over devices of (elements/replication) == global elements."""
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = (4 * a, 2 * b, 4 * c)
        d = ParamDef(shape, ("fsdp", None, "tp"))
        spec = storage_spec(d, mesh, "fcdp")
        used = set()
        for e in spec:
            if e is None:
                continue
            used.update(e if isinstance(e, tuple) else (e,))
        rep = 1
        for ax, n in (("pod", 2), ("data", 2), ("model", 2)):
            if ax not in used:
                rep *= n
        n_dev = 8
        shard_elems = d.size() / (n_dev / rep)
        assert shard_elems * n_dev / rep == d.size()

"""Gather-fused collective matmul (kernels/collective_matmul.py).

Three layers of coverage, matching the module's bit-exactness contract:

  * kernel vs oracle: the Pallas per-chunk matmul (interpret mode) and
    both rings against the kernels/ref.py mirrors, bit-exact, including
    non-divisible block shapes;
  * plan-level gating: which (strategy, ParamDef, mesh) combinations
    the eligibility rule in core/strategy.gather_plan admits;
  * end-to-end: a real train step fused vs unfused is bit-identical
    (losses AND updated params), and mode='both' matches its own ring
    oracles exactly while staying close to the unfused trajectory.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import collective_matmul as cm
from repro.kernels import ref

P = jax.sharding.PartitionSpec


# ---------------------------------------------------------------------------
# per-chunk Pallas matmul vs the tile-loop oracle (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.pallas_interpret
@pytest.mark.parametrize("shape", [(128, 64, 128),   # exact grid
                                   (7, 96, 100),     # both dims ragged
                                   (130, 32, 257),   # spills one tile
                                   (1, 16, 1)])      # degenerate
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_chunk_bit_exact(shape, dtype, rng):
    M, K, N = shape
    x = jnp.asarray(rng.normal(0, 1, (M, K)), dtype)
    w = jnp.asarray(rng.normal(0, 1, (K, N)), dtype)
    got = cm.matmul_chunk(x, w, interpret=True)
    want = ref.matmul_chunk_ref(x, w)
    assert got.dtype == want.dtype
    assert jnp.array_equal(got, want), "pallas chunk != tile-loop oracle"


@pytest.mark.pallas_interpret
def test_matmul_chunk_block_shapes(rng):
    """Different tilings agree bit-for-bit: K is whole per program, so
    the tiling never re-associates the contraction."""
    x = jnp.asarray(rng.normal(0, 1, (100, 48)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (48, 200)), jnp.float32)
    o1 = cm.matmul_chunk(x, w, block_m=128, block_n=128, interpret=True)
    o2 = cm.matmul_chunk(x, w, block_m=32, block_n=64, interpret=True)
    assert jnp.array_equal(o1, o2)


# ---------------------------------------------------------------------------
# the rings, inside shard_map on real device meshes
# ---------------------------------------------------------------------------

def _ring_ag(mesh, axis, x, w, **kw):
    """ring_ag_matmul with x replicated and w column-sharded over axis."""
    fn = lambda x_, w_: cm.ring_ag_matmul(x_, w_, axis, **kw)
    return jax.jit(jax.shard_map(fn, mesh=mesh,
                                 in_specs=(P(), P(None, axis)),
                                 out_specs=P(), check_vma=False))(x, w)


@pytest.mark.parametrize("axis,n", [("data", 4), ("model", 2)])
def test_ring_ag_matmul_vs_unfused(mesh2, rng, axis, n):
    """The fused forward == gather-then-matmul, bit-for-bit (the
    column-concat identity the whole feature rests on)."""
    x = jnp.asarray(rng.normal(0, 1, (16, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (24, 8 * n)), jnp.float32)
    base = lambda x_, w_: x_ @ jax.lax.all_gather(w_, axis, axis=1,
                                                  tiled=True)
    want = jax.jit(jax.shard_map(base, mesh=mesh2,
                                 in_specs=(P(), P(None, axis)),
                                 out_specs=P(), check_vma=False))(x, w)
    got = _ring_ag(mesh2, axis, x, w)
    assert jnp.array_equal(got, want)


def test_ring_ag_matmul_vs_oracle(mesh2, rng):
    """Ring output == the per-chunk oracle laid out in rank order."""
    n = 4
    x = jnp.asarray(rng.normal(0, 1, (8, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (16, 12 * n)), jnp.float32)
    w_chunks = jnp.stack(jnp.split(w, n, axis=1))       # [n, K, Nc]
    got = _ring_ag(mesh2, "data", x, w)
    assert jnp.array_equal(got, ref.ag_matmul_ref(x, w_chunks))


@pytest.mark.pallas_interpret
def test_ring_ag_matmul_pallas_impl(mesh2, rng):
    """impl='pallas' (interpret) through the ring == the tile-loop
    oracle per chunk -- ragged Nc exercises the pad-and-slice path."""
    n = 2
    x = jnp.asarray(rng.normal(0, 1, (10, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (16, 18 * n)), jnp.float32)
    got = _ring_ag(mesh2, "model", x, w, impl="pallas", interpret=True,
                   block_m=8, block_n=16)
    w_chunks = jnp.split(w, n, axis=1)
    want = jnp.concatenate(
        [ref.matmul_chunk_ref(x, c, block_m=8, block_n=16)
         for c in w_chunks], axis=1)
    assert jnp.array_equal(got, want)


def test_ring_ag_matmul_batched_x(mesh2, rng):
    """Arbitrary-rank activations ([B, S, K]) flow through the ring."""
    x = jnp.asarray(rng.normal(0, 1, (2, 6, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (16, 8 * 4)), jnp.float32)
    base = lambda x_, w_: x_ @ jax.lax.all_gather(w_, "data", axis=1,
                                                  tiled=True)
    want = jax.jit(jax.shard_map(base, mesh=mesh2,
                                 in_specs=(P(), P(None, "data")),
                                 out_specs=P(), check_vma=False))(x, w)
    assert jnp.array_equal(_ring_ag(mesh2, "data", x, w), want)


def test_ring_matmul_rs_vs_ref(mesh2, rng):
    """Per-rank reduce-scatter chunks match the oracle's exact
    hop-by-hop accumulation order (bit-exact, not allclose)."""
    n = 4
    a = jnp.asarray(rng.normal(0, 1, (n, 6, 10)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 1, (n, 10, 8 * n)), jnp.float32)

    def body(a_, b_):
        out = cm.ring_matmul_rs(a_[0], b_[0], "data")
        return out[None]
    got = jax.jit(jax.shard_map(body, mesh=mesh2,
                                in_specs=(P("data"), P("data")),
                                out_specs=P("data"), check_vma=False))(a, b)
    for r in range(n):
        assert jnp.array_equal(got[r], ref.matmul_rs_ref(a, b, r)), r


def test_ring_matmul_rs_sums_to_psum_scatter(mesh2, rng):
    """Summed over ranks (tolerantly): the fused RS == the unfused
    matmul + psum_scatter it replaces."""
    n = 4
    a = jnp.asarray(rng.normal(0, 1, (n, 6, 10)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 1, (n, 10, 8 * n)), jnp.float32)

    def base(a_, b_):
        return jax.lax.psum_scatter(a_[0] @ b_[0], "data",
                                    scatter_dimension=1, tiled=True)[None]
    want = jax.jit(jax.shard_map(base, mesh=mesh2,
                                 in_specs=(P("data"), P("data")),
                                 out_specs=P("data"), check_vma=False))(a, b)

    def body(a_, b_):
        return cm.ring_matmul_rs(a_[0], b_[0], "data")[None]
    got = jax.jit(jax.shard_map(body, mesh=mesh2,
                                in_specs=(P("data"), P("data")),
                                out_specs=P("data"), check_vma=False))(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# custom_vjp: gradients
# ---------------------------------------------------------------------------

def _grads(mesh, axis, x, w, mode):
    def loss(x_, w_):
        y = cm.fused_matmul(x_, w_, axis, mode)
        return jnp.sum(y * y)
    fn = jax.grad(loss, argnums=(0, 1))
    return jax.jit(jax.shard_map(fn, mesh=mesh,
                                 in_specs=(P(), P(None, axis)),
                                 out_specs=(P(), P(None, axis)),
                                 check_vma=False))(x, w)


def test_ag_matmul_grad_bit_parity(mesh2, rng):
    """mode='ag_matmul' backward replays the unfused op sequence, so
    BOTH cotangents are bit-identical to the unfused path -- the
    property that makes whole training trajectories bit-identical."""
    x = jnp.asarray(rng.normal(0, 1, (6, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (16, 8 * 4)), jnp.float32)

    def base_loss(x_, w_):
        y = x_ @ jax.lax.all_gather(w_, "data", axis=1, tiled=True)
        return jnp.sum(y * y)
    want = jax.jit(jax.shard_map(jax.grad(base_loss, argnums=(0, 1)),
                                 mesh=mesh2,
                                 in_specs=(P(), P(None, "data")),
                                 out_specs=(P(), P(None, "data")),
                                 check_vma=False))(x, w)
    got = _grads(mesh2, "data", x, w, "ag_matmul")
    assert jnp.array_equal(got[0], want[0])
    assert jnp.array_equal(got[1], want[1])


def test_both_grad_vs_ring_oracles(mesh2, rng):
    """mode='both' re-associates the dx sum, so it is exact against its
    OWN ring oracles (dx: fused_bwd_dx_ref per rank; dw: matmul_rs_ref)
    -- and only close to the unfused gradients."""
    n = 4
    x = jnp.asarray(rng.normal(0, 1, (6, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (16, 8 * n)), jnp.float32)
    w_chunks = jnp.stack(jnp.split(w, n, axis=1))       # [n, K, Nc]

    def loss(x_, w_):
        y = cm.fused_matmul(x_, w_, "data", "both")
        return jnp.sum(y * y)

    def per_rank(x_, w_):
        dx, dw = jax.grad(loss, argnums=(0, 1))(x_, w_)
        return dx[None], dw
    dx_all, dw = jax.jit(jax.shard_map(
        per_rank, mesh=mesh2, in_specs=(P(), P(None, "data")),
        out_specs=(P("data"), P(None, "data")), check_vma=False))(x, w)

    y = ref.ag_matmul_ref(x, w_chunks)
    g = 2.0 * y                                         # d(sum y^2)/dy
    for r in range(n):
        assert jnp.array_equal(dx_all[r],
                               ref.fused_bwd_dx_ref(g, w_chunks, r)), r
    a_chunks = jnp.broadcast_to(x.T[None], (n,) + x.T.shape)
    b_chunks = jnp.broadcast_to(g[None], (n,) + g.shape)
    want_dw = jnp.concatenate(
        [ref.matmul_rs_ref(a_chunks, b_chunks, r) for r in range(n)],
        axis=1)
    assert jnp.array_equal(dw, want_dw)
    # and the unfused gradient is the same sum in a different order
    base = lambda x_, w_: jnp.sum(
        (x_ @ jax.lax.all_gather(w_, "data", axis=1, tiled=True)) ** 2)
    want = jax.jit(jax.shard_map(jax.grad(base, argnums=(0, 1)), mesh=mesh2,
                                 in_specs=(P(), P(None, "data")),
                                 out_specs=(P(), P(None, "data")),
                                 check_vma=False))(x, w)
    np.testing.assert_allclose(np.asarray(dx_all[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# plan-level eligibility gating (core/strategy.gather_plan)
# ---------------------------------------------------------------------------

def _plan(mode, pdef, mesh, fused="ag_matmul"):
    from repro.core.strategy import resolve_strategy
    s = mode if not isinstance(mode, str) else resolve_strategy(mode)
    return s.gather_plan(pdef, mesh, min_shard_size=0, fused_matmul=fused)


def _proj(**kw):
    from repro.core.partition import ParamDef
    kw.setdefault("fusable", True)
    return ParamDef((256, 128), ("tp", "fsdp"), **kw)


def test_gating_eligible_fcdp_multipod(mesh3):
    p = _plan("fcdp", _proj(), mesh3)
    assert p.is_fused and p.fused == "ag_matmul"
    assert len(p.intra_axes) == 1
    # and the knob off means no fusing anywhere
    assert not _plan("fcdp", _proj(), mesh3, fused="none").is_fused


def test_gating_eligible_stacked_and_zero3(mesh3, mesh2):
    from repro.core.partition import ParamDef
    stacked = ParamDef((4, 256, 128), ("stack", "tp", "fsdp"), fusable=True)
    assert _plan("fcdp", stacked, mesh3).is_fused
    # zero3 regathers stage 2 per use on any mesh: always fusable
    assert _plan("zero3", _proj(), mesh3).is_fused
    assert _plan("zero3", _proj(), mesh2).is_fused


def test_gating_declines_without_opt_in(mesh3):
    """Same shape/dims as a projection, but no ParamDef.fusable -- an
    embedding table is consumed via take, not matmul, and must never be
    wrapped in a FusedParam."""
    assert not _plan("fcdp", _proj(fusable=False), mesh3).is_fused


def test_gating_declines_shapes_and_frozen(mesh3):
    from repro.core.partition import ParamDef
    declined = [
        _proj(frozen=True),                              # FCDP-Comm
        ParamDef((256, 128), ("fsdp", "tp"), fusable=True),   # input-dim
        ParamDef((128,), ("fsdp",), fusable=True),       # 1-D
        # elementwise-consumed leaf (rwkv maa_base shape): the plan rule
        # cannot tell it from a projection -- ParamDef.fusable (default
        # False) is the def-site contract that keeps it unfused
        ParamDef((6, 128), (None, "fsdp")),
    ]
    for pdef in declined[1:]:
        assert not _plan("fcdp", pdef, mesh3).is_fused, pdef
    assert not _plan("fcdp", declined[0], mesh3).is_fused


def test_gating_declines_cached_full_weight(mesh2):
    """Single-pod fcdp/zeropp cache the FULLY gathered weight
    (cache_after=2): no per-use stage-2 gather remains to fuse."""
    for mode in ("fcdp", "zeropp"):
        p = _plan(mode, _proj(), mesh2)
        assert p.cache_after == 2
        assert not p.is_fused, mode


def test_gating_strategy_opt_out(mesh3):
    """A strategy subclass (or mixed-sharding group) that declines keeps
    its unfused stage-2 gather even for eligible leaves."""
    from repro.core.strategy import FCDP

    class Declining(FCDP):
        name = "declining_fused"
        supports_fused_matmul = False

    assert not _plan(Declining(), _proj(), mesh3).is_fused
    assert _plan(FCDP(), _proj(), mesh3).is_fused     # control


def test_sysconfig_validates_fused_knobs():
    from repro.configs.base import SystemConfig
    SystemConfig(fused_matmul="both", fused_impl="pallas")   # ok
    with pytest.raises(ValueError):
        SystemConfig(fused_matmul="everything")
    with pytest.raises(ValueError):
        SystemConfig(fused_impl="cuda")


# ---------------------------------------------------------------------------
# end-to-end: train-step bit-parity fused on vs off
# ---------------------------------------------------------------------------

def _train(mesh, mode, fused, batches):
    from repro.configs.base import (ModelConfig, OptimizerConfig, RunConfig,
                                    ShapeCell, SystemConfig)
    from repro.core.engine import StepBundle
    from repro.optim.adamw import init_opt_state
    cfg = ModelConfig(name="smoke-dense", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256)
    sysc = SystemConfig(mode=mode, min_shard_size=8, fused_matmul=fused)
    run = RunConfig(model=cfg, shape=ShapeCell("t", "train", 64, 8),
                    system=sysc,
                    optimizer=OptimizerConfig(total_steps=3, warmup_steps=1))
    b = StepBundle(run, mesh)
    n_fused = sum(int(getattr(p, "is_fused", False))
                  for p in jax.tree.leaves(
                      b.plan_leaves, is_leaf=lambda x: hasattr(x, "fused")))
    step = b.make_train_step()
    params = b.init_all_params(seed=0)
    tp, fp = b.split(params)
    opt = jax.jit(functools.partial(init_opt_state, sys=sysc))(tp)
    losses = []
    for batch in batches:
        tp, opt, m = step(tp, fp, opt, batch)
        losses.append(float(m["loss"]))
    return n_fused, losses, tp


def _batches(rng, n=2):
    return [{"ids": jnp.asarray(rng.integers(1, 256, (8, 64)), jnp.int32),
             "labels": jnp.asarray(rng.integers(1, 256, (8, 64)), jnp.int32),
             "mask": jnp.ones((8, 64), bool)} for _ in range(n)]


def test_train_step_bit_parity(mesh3, rng):
    batches = _batches(rng)
    n_off, losses_off, params_off = _train(mesh3, "fcdp", "none", batches)
    n_on, losses_on, params_on = _train(mesh3, "fcdp", "ag_matmul", batches)
    assert n_off == 0 and n_on > 0
    assert losses_on == losses_off          # float-exact, not allclose
    leaves_off = jax.tree.leaves(params_off)
    leaves_on = jax.tree.leaves(params_on)
    assert all(jnp.array_equal(a, b)
               for a, b in zip(leaves_on, leaves_off))


def test_train_step_both_mode_trains(mesh3, rng):
    """mode='both' re-associates the bf16 backward: not bit-identical,
    but the trajectory stays within a tight drift bound."""
    batches = _batches(rng)
    _, losses_off, _ = _train(mesh3, "fcdp", "none", batches)
    n_on, losses_on, _ = _train(mesh3, "fcdp", "both", batches)
    assert n_on > 0
    drift = max(abs(a - b) / abs(b)
                for a, b in zip(losses_on, losses_off))
    assert drift < 5e-2, drift

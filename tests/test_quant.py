"""Quantized collectives (qwZ stage-1 weight gather + int8 kernel paths).

Three layers of coverage:
  * kernel: Pallas quant kernels (interpret mode) bit-exact against the
    kernels/ref.py jnp oracles across shapes/dtypes, incl. tensors that
    are not a multiple of the 256 block;
  * plan: the strategy-level qwZ gates (param_compress config, per-group
    supports_quantized_gather, the sub-block small-leaf gate);
  * e2e: training under param_compress='int8_pod' tracks the exact run
    within a bounded loss drift, stacks with FCDP host caching (single
    quantized fwd stage-1 gather; backward stays gather-free), and
    composes with the async grad-reduce stream.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.quant import BLOCK

pytestmark = pytest.mark.pallas_interpret

# ---------------------------------------------------------------------------
# kernel vs oracle (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nb", [1, 3, 8, 17])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_blocks_bit_exact(nb, dtype, rng):
    x = jnp.asarray(rng.normal(0, 3, (nb, BLOCK)), dtype).astype(jnp.float32)
    qk, sk = ops.int8_quantize_blocks(x, impl="pallas", interpret=True)
    qr, sr = ref.int8_quantize_blocks_ref(x)
    assert qk.dtype == jnp.int8 and sk.shape == (nb, 1)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))


def test_quantize_blocks_zero_and_const_blocks(rng):
    """All-zero blocks hit the scale floor; constant blocks hit +-127."""
    x = jnp.concatenate([jnp.zeros((1, BLOCK)),
                         jnp.full((1, BLOCK), 7.5),
                         jnp.full((1, BLOCK), -0.25)]).astype(jnp.float32)
    qk, sk = ops.int8_quantize_blocks(x, impl="pallas", interpret=True)
    qr, sr = ref.int8_quantize_blocks_ref(x)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
    assert np.all(np.asarray(qk[0]) == 0)
    assert np.all(np.abs(np.asarray(qk[1:])) == 127)


@pytest.mark.parametrize("nb", [1, 5, 16])
def test_dequantize_blocks_bit_exact(nb, rng):
    q = jnp.asarray(rng.integers(-127, 128, (nb, BLOCK)), jnp.int8)
    s = jnp.asarray(2.0 ** rng.integers(-8, 3, (nb, 1)), jnp.float32)
    out = ops.int8_dequantize_blocks(q, s, impl="pallas", interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.int8_dequantize_blocks_ref(q, s)))


@pytest.mark.parametrize("n,nb", [(2, 5), (4, 8), (3, 1), (8, 17)])
def test_dequant_accumulate_bit_exact_pow2(n, nb, rng):
    """Power-of-two scales make every product and sum exactly
    representable, so kernel-vs-oracle must agree to the bit."""
    q = jnp.asarray(rng.integers(-127, 128, (n, nb, BLOCK)), jnp.int8)
    s = jnp.asarray(2.0 ** rng.integers(-8, 2, (n, nb, 1)), jnp.float32)
    out = ops.int8_dequant_accumulate(q, s, impl="pallas", interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.int8_dequant_acc_ref(q, s)))


def test_dequant_accumulate_random_scales_close(rng):
    """Arbitrary scales: FMA fusion differences bound the comparison to
    last-ulp (the accumulate order itself is identical)."""
    q = jnp.asarray(rng.integers(-127, 128, (4, 8, BLOCK)), jnp.int8)
    s = jnp.asarray(np.abs(rng.normal(0, 0.05, (4, 8, 1))) + 1e-4,
                    jnp.float32)
    out = ops.int8_dequant_accumulate(q, s, impl="pallas", interpret=True)
    # atol covers near-cancelling sums where relative error is unbounded
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.int8_dequant_acc_ref(q, s)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(100,), (256,), (300, 7), (31, 33)])
def test_quantize_pad_path_impl_agreement(shape, rng):
    """Non-multiple-of-256 tensors take the shared pad path in
    grad_compress._quantize: jnp and interpret-Pallas must agree."""
    from repro.core.grad_compress import _quantize
    g = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    qj, sj = _quantize(g, impl="jnp")
    qp, sp = _quantize(g, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(qj), np.asarray(qp))
    np.testing.assert_array_equal(np.asarray(sj), np.asarray(sp))
    # round-trip error bounded by half an lsb per element
    deq = ops.int8_dequantize_blocks(qj, sj, impl="jnp").reshape(-1)
    flat = np.asarray(g, np.float32).reshape(-1)
    lsb = np.asarray(sj)[:, 0].repeat(BLOCK)[: flat.size]
    assert np.all(np.abs(np.asarray(deq)[: flat.size] - flat) <= 0.5 * lsb)


# ---------------------------------------------------------------------------
# plan-level gating
# ---------------------------------------------------------------------------


def _plan(pdef, mesh3, **kw):
    from repro.core.strategy import get_strategy
    return get_strategy("fcdp").gather_plan(pdef, mesh3, min_shard_size=8,
                                            **kw)


def test_param_compress_gate_big_vs_small_leaf(mesh3):
    from repro.core.partition import ParamDef
    big = ParamDef((4, 64, 64), ("stack", "fsdp", "tp"))
    small = ParamDef((4, 64), ("stack", "fsdp"))   # 16 elems/slice shard
    p_big = _plan(big, mesh3, param_compress=True, compress_bwd=True)
    p_small = _plan(small, mesh3, param_compress=True, compress_bwd=True)
    assert p_big.compress_fwd and p_big.compress_bwd
    # sub-block shards would pay MORE wire bytes quantized than exact
    assert not p_small.compress_fwd and not p_small.compress_bwd
    # and the knob itself defaults off
    p_off = _plan(big, mesh3)
    assert not p_off.compress_fwd and not p_off.compress_bwd


def test_frozen_leaves_never_quantize(mesh3):
    from repro.core.partition import ParamDef
    frozen = ParamDef((4, 64, 64), ("stack", "fsdp", "tp"), frozen=True)
    p = _plan(frozen, mesh3, param_compress=True, compress_bwd=True)
    assert not p.compress_fwd and not p.compress_bwd


def test_config_validation():
    from repro.configs.base import SystemConfig
    with pytest.raises(ValueError):
        SystemConfig(param_compress="int4")
    with pytest.raises(ValueError):
        SystemConfig(quant_impl="triton")
    s = SystemConfig(param_compress="int8_pod", quant_impl="pallas_interpret")
    assert s.param_compress == "int8_pod"


def test_composite_group_gating(mesh3):
    """A declining group inside a quantized bundle keeps its exact bf16
    stage-1 gather; the fcdp trunk quantizes."""
    from repro.configs.base import ModelConfig, SystemConfig
    from repro.core.partition import label_tree
    from repro.core.strategy import FCDP, register_strategy, resolve_strategies
    from repro.models.lm import LM

    class FCDPNoQuant(FCDP):
        name = "fcdp_nq"
        supports_quantized_gather = False

    register_strategy(FCDPNoQuant)
    cfg = ModelConfig(name="smoke-dense", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256)
    sysc = SystemConfig(mode="fcdp", min_shard_size=8,
                        param_compress="int8_pod",
                        mode_overrides=(("head", "fcdp_nq"),))
    model = LM(cfg, sysc, mesh3)
    assert not model.plans["head"].compress_fwd        # declining group
    assert model.plans["embed"].compress_fwd           # fcdp trunk
    assert model.plans["blocks"]["pos0"]["attn"]["wq"].compress_fwd
    # sub-block norm leaves stay exact inside the quantizing trunk too
    assert not model.plans["blocks"]["pos0"]["attn"]["norm"].compress_fwd


# ---------------------------------------------------------------------------
# e2e: loss drift, caching, async composability
# ---------------------------------------------------------------------------

_CFG = dict(name="smoke-dense", family="dense", num_layers=2, d_model=64,
            num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256)


def _train(mesh3, rng, n_steps=3, microbatch=0, **sys_kw):
    from repro.configs.base import (ModelConfig, OptimizerConfig, RunConfig,
                                    ShapeCell, SystemConfig)
    from repro.core.engine import StepBundle
    from repro.optim.adamw import init_opt_state
    sysc = SystemConfig(mode="fcdp", min_shard_size=8, **sys_kw)
    run = RunConfig(model=ModelConfig(**_CFG), shape=ShapeCell(
        "t", "train", 64, 8), system=sysc,
        optimizer=OptimizerConfig(total_steps=8, warmup_steps=1),
        microbatch=microbatch)
    b = StepBundle(run, mesh3)
    step = b.make_train_step()
    params = b.init_all_params(seed=0)
    tp, fp = b.split(params)
    opt = jax.jit(functools.partial(init_opt_state, sys=sysc))(tp)
    losses = []
    r = np.random.default_rng(7)
    for _ in range(n_steps):
        batch = {"ids": jnp.asarray(r.integers(1, 256, (8, 64)), jnp.int32),
                 "labels": jnp.asarray(r.integers(1, 256, (8, 64)),
                                       jnp.int32),
                 "mask": jnp.ones((8, 64), bool)}
        tp, opt, m = step(tp, fp, opt, batch)
        losses.append(float(m["loss"]))
    return losses, b


def test_e2e_quantized_gather_loss_drift(mesh3, rng):
    exact, _ = _train(mesh3, rng)
    quant, b = _train(mesh3, rng, param_compress="int8_pod")
    drift = max(abs(a - e) / abs(e) for a, e in zip(quant, exact))
    assert drift < 1e-2, (quant, exact)
    # and the step still pays only ONE (quantized) stage-1 gather per
    # leaf per step: pod-axis AG bytes shrink vs the exact run
    from repro.launch.roofline import collect_collectives
    sizes = {a: b.mi.size(a) for a in b.mi.axis_names}
    s_q = collect_collectives(
        b.make_train_step().trace(*b.train_input_sds()).jaxpr, sizes)
    _, b_e = _train(mesh3, rng, n_steps=1)
    s_e = collect_collectives(
        b_e.make_train_step().trace(*b_e.train_input_sds()).jaxpr, sizes)
    assert s_q.by_op_axis["all_gather/pod"] \
        < 0.55 * s_e.by_op_axis["all_gather/pod"]


def test_async_reduce_composes_with_int8(mesh3, rng):
    """Satellite: async_grad_reduce no longer requires
    grad_compress='none' -- the int8 reduce rides the async stream.
    Block boundaries differ (leaf-level vs per-layer quantization), so
    the comparison is tolerance-based, not bit-exact."""
    from repro.core.schedule import async_reduce_enabled
    sync, _ = _train(mesh3, rng, microbatch=2, grad_compress="int8_pod",
                     param_compress="int8_pod")
    async_, b = _train(mesh3, rng, microbatch=2, grad_compress="int8_pod",
                       param_compress="int8_pod", async_grad_reduce=True)
    assert async_reduce_enabled(b.run, b.strategy, b.mi)
    for a, s in zip(async_, sync):
        assert abs(a - s) / abs(s) < 5e-2, (async_, sync)


def test_quantized_gather_shard_map_impl_agreement(mesh3, rng):
    """quantized_stage1_gather under shard_map: the pallas_interpret
    kernel path must match the jnp path bit-for-bit (same quant grid)."""
    from jax.sharding import PartitionSpec as P
    from repro.core.grad_compress import quantized_stage1_gather
    w = jnp.asarray(rng.normal(0, 1, (16, 64)), jnp.float32)

    def run(impl):
        f = jax.shard_map(
            lambda x: quantized_stage1_gather(x, "pod", 0, False, impl),
            mesh=mesh3, in_specs=P("pod"), out_specs=P(),
            check_vma=False)    # all_gather output is VMA-varying
        return np.asarray(jax.jit(f)(w))

    out_jnp = run("jnp")
    np.testing.assert_array_equal(out_jnp, run("pallas_interpret"))
    # the gather is lossy-but-bounded: within half an lsb per block
    assert np.max(np.abs(out_jnp - np.asarray(w))) <= 0.5 * np.max(
        np.abs(np.asarray(w))) / 127.0 + 1e-6

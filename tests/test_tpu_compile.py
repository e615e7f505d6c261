"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology and refuses what the chip
would refuse (misaligned tiles, too much VMEM, a bf16 matmul
accumulator), which interpret-mode tests cannot see. Each kernel is
compiled at the widths the trainer runs (qwen2.5-3b: 16 heads x 128 x
4096 tokens, 2048x2048 leaves, contractions 2048 and 11008) and must
lower to a Mosaic ``tpu_custom_call``; so must the fused attention of
the fcdp LoRA train step, forward and backward. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports every
test file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import collective_matmul as cm
from repro.kernels import ops
from repro.kernels.quant import BLOCK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _mm(kdim):
    fn = jax.jit(cm.matmul_chunk,
                 static_argnames=("block_m", "block_n", "interpret"))
    return fn, [((4096, kdim), jnp.bfloat16), ((kdim, 1024), jnp.bfloat16)], {}


def _attention_train(q, k, v, do):
    """The fused attention kernel's forward and backward."""
    out, pull = jax.vjp(lambda *a: ops.causal_attention_train(
        *a, softmax_scale=128 ** -0.5), q, k, v)
    return (out, *pull(do))


NB = 2048 * 2048 // BLOCK          # quant blocks of one 2048x2048 leaf

CASES = {
    "flash_attention": (ops.flash_attention,
                        [((1, 4096, 16, 128), jnp.bfloat16)] * 3,
                        {"causal": True, "impl": "pallas"}),
    "causal_attention_train": (jax.jit(_attention_train),
                               [((1, 4096, 16, 128), jnp.bfloat16)] * 4,
                               {}),
    "int8_quantize_blocks": (ops.int8_quantize_blocks,
                             [((NB, BLOCK), jnp.float32)],
                             {"impl": "pallas"}),
    "int8_dequantize_blocks": (ops.int8_dequantize_blocks,
                               [((NB, BLOCK), jnp.int8),
                                ((NB, 1), jnp.float32)],
                               {"impl": "pallas"}),
    "int8_dequant_accumulate": (ops.int8_dequant_accumulate,
                                [((2, NB, BLOCK), jnp.int8),
                                 ((2, NB, 1), jnp.float32)],
                                {"impl": "pallas"}),
    "matmul_chunk_k2048": _mm(2048),
    "matmul_chunk_k11008": _mm(11008),
    "ssm_scan": (ops.ssm_scan, [((1, 4096, 8192), jnp.float32)] * 2,
                 {"impl": "pallas"}),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes, static = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def _lora_step(topo, model, seq_len, **system):
    """The fcdp LoRA train step of ``model`` on one described chip, over
    one row of ``seq_len`` tokens, compiled."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs.base import RunConfig, ShapeCell, SystemConfig
    from repro.core.engine import StepBundle
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    sysc = SystemConfig(mode="fcdp", peft=True, activation_policy="block_io",
                        loss_chunk=128, min_shard_size=8, **system)
    bundle = StepBundle(RunConfig(model=model, system=sysc,
                                  shape=ShapeCell("t", "train", seq_len, 1)),
                        mesh)
    return bundle.make_train_step().lower(*bundle.train_input_sds()).compile()


def test_fcdp_lora_train_step_compiles_for_v5e(topo, no_persistent_cache):
    """The whole fcdp LoRA train step on one described chip, at a toy
    width: layer scan, the chunked attention path (the smoke config's
    head_dim of 16 does not tile the fused kernel), the loss, and the
    remat policy's host offload, which must land in host memory
    (``S(5)``) without a sublane-misaligned host update the compiler
    refuses."""
    from repro.configs.qwen2_5_3b import SMOKE
    text = _lora_step(topo, SMOKE, 512).as_text()
    assert "S(5)" in text and "tpu_custom_call" not in text


def _op_names(text, pred):
    return [m.group(1) for line in text.splitlines() if pred(line)
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def test_fcdp_lora_train_step_runs_attention_on_kernel_for_v5e(
        topo, no_persistent_cache):
    """The same step at qwen's head_dim of 128 over rows of 2048 runs
    attention on the fused kernel: its forward, and its dK/dV and dQ
    kernels in the backward, with no kv-block loop of the chunked path
    under the attention scope; and it plans no more device memory than
    the step forced onto the chunked path (``attn_impl='jnp'``), whose
    backward stacks each block pair's f32 probabilities."""
    from repro.configs.qwen2_5_3b import SMOKE
    model = dataclasses.replace(SMOKE, head_dim=128)
    kernel = _lora_step(topo, model, 2048)
    chunked = _lora_step(topo, model, 2048, attn_impl="jnp")
    calls = _op_names(kernel.as_text(), lambda l: "tpu_custom_call" in l)
    assert all("attention/" in n for n in calls) and calls
    assert any("transpose(" not in n for n in calls)            # forward
    assert any("flash_mha_bwd_dkv" in n for n in calls)
    assert any("flash_mha_bwd_dq" in n for n in calls)

    def attention_loops(text):
        return _op_names(text, lambda l: " while(" in l and "attention/" in l)
    assert not attention_loops(kernel.as_text())
    assert attention_loops(chunked.as_text())     # what the check would see

    def planned(c):
        ma = c.memory_analysis()
        return (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert planned(kernel) <= planned(chunked)

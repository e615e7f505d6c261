"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology and refuses what the chip
would refuse (misaligned tiles, too much VMEM, a bf16 matmul
accumulator), which interpret-mode tests cannot see. Each kernel is
compiled at the widths the trainer runs (qwen2.5-3b: 16 heads x 128 x
4096 tokens, 2048x2048 leaves, contractions 2048 and 11008) and must
lower to a Mosaic ``tpu_custom_call``. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports every
test file.
"""
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


NB = 2048 * 2048 // BLOCK          # quant blocks of one 2048x2048 leaf

CASES = {
    "flash_attention": (ops.flash_attention,
                        [((1, 4096, 16, 128), jnp.bfloat16)] * 3,
                        {"causal": True, "impl": "pallas"}),
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


def test_fcdp_lora_train_step_compiles_for_v5e(topo, no_persistent_cache):
    """The whole fcdp LoRA train step on one described chip, at a toy
    width: layer scan, chunked attention and loss, and the remat
    policy's host offload, which must land in host memory (``S(5)``)
    without a sublane-misaligned host update the compiler refuses."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs.base import RunConfig, ShapeCell, SystemConfig
    from repro.configs.qwen2_5_3b import SMOKE
    from repro.core.engine import StepBundle
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    sysc = SystemConfig(mode="fcdp", peft=True, activation_policy="block_io",
                        loss_chunk=128, min_shard_size=8)
    bundle = StepBundle(RunConfig(model=SMOKE, system=sysc,
                                  shape=ShapeCell("t", "train", 512, 1)),
                        mesh)
    compiled = bundle.make_train_step().lower(
        *bundle.train_input_sds()).compile()
    assert "S(5)" in compiled.as_text()

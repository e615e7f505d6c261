"""FLOP counts and the table of peaks, on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import flops
from benchmarks.chip.families import dense

SMOKE = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=512)
LORA = {"rank": 8, "alpha": 16.0, "targets": ["wq", "wk", "wv", "wo"]}


def test_model_flops_by_hand_full_and_lora():
    S = 256
    # one layer: q 64x64, k and v 64x32, o 64x64, gate/in/out 3 x 64x128
    layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert layer == 36864
    head = 64 * 512
    attn = 2 * 4 * (S / 2) * 4 * 16          # layers x 4 x S/2 x heads x hd
    fwd = 2 * (2 * layer + head) + attn
    assert dense.model_flops_per_token(SMOKE, S, None) == 3 * fwd
    # adapters per layer: rank 8 x (in + out) for q, k, v, o
    adapters = 2 * 8 * ((64 + 64) + (64 + 32) + (64 + 32) + (64 + 64))
    fwd_lora = fwd + 2 * adapters
    assert dense.model_flops_per_token(SMOKE, S, LORA) == (
        2 * fwd_lora + 2 * adapters)


def test_matmul_flops_of_a_compiled_program():
    def f(a, b, c):
        return jnp.tanh(a @ b) @ c

    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    c = jnp.ones((32, 16), jnp.float32)
    text = jax.jit(f).lower(a, b, c).compile().as_text()
    costs = flops.hlo_op_costs(text)
    assert sum(c_.flops for c_ in costs.values()) == (
        2 * 64 * 128 * 32 + 2 * 64 * 32 * 16)


def test_convolution_counts_only_taps_on_input():
    # a projection XLA writes as a convolution whose padded window of 16
    # meets one real input element per output position
    text = """HloModule m, entry_computation_layout={()->bf16[4096,16,128]}

ENTRY %main (p0: bf16[4096,2048,1], p1: bf16[16,128,2048]) -> bf16[4096,16,128] {
  %p0 = bf16[4096,2048,1]{0,1,2} parameter(0)
  %p1 = bf16[16,128,2048]{2,1,0} parameter(1)
  ROOT %convolution.1 = bf16[4096,16,128]{0,2,1} convolution(%p0, %p1), window={size=16 pad=15_15 rhs_reversal=1}, dim_labels=bf0_0oi->b0f
}
"""
    cost = flops.hlo_op_costs(text)["convolution.1"]
    assert cost.flops == 2 * 4096 * 16 * 128 * 2048
    assert cost.bytes == 2 * (4096 * 2048 + 16 * 128 * 2048 + 4096 * 16 * 128)


def test_bytes_of_a_fusion_that_slices_and_updates_in_place():
    text = """HloModule m

%fused (param_0: bf16[36,64,64], param_1: bf16[8,64], param_2: s32[]) -> bf16[36,8,64] {
  %param_0 = bf16[36,64,64]{2,1,0} parameter(0)
  %param_2 = s32[] parameter(2)
  %c = s32[] constant(0)
  %ds = bf16[1,64,64]{2,1,0} dynamic-slice(%param_0, %param_2, %c, %c), dynamic_slice_sizes={1,64,64}
  %w = bf16[64,64]{1,0} bitcast(%ds)
  %param_1 = bf16[8,64]{1,0:S(1)} parameter(1)
  %dot = bf16[8,64]{1,0} dot(%param_1, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %upd = bf16[1,8,64]{2,1,0} bitcast(%dot)
  %buf = bf16[36,8,64]{2,1,0} broadcast(%c), dimensions={}
  ROOT %dus = bf16[36,8,64]{2,1,0} dynamic-update-slice(%buf, %upd, %param_2, %c, %c)
}

ENTRY %main (a: bf16[36,64,64], b: bf16[8,64], i: s32[]) -> bf16[36,8,64] {
  %a = bf16[36,64,64]{2,1,0} parameter(0)
  %b = bf16[8,64]{1,0:S(1)} parameter(1)
  %i = s32[] parameter(2)
  ROOT %fusion.7 = bf16[36,8,64]{2,1,0} fusion(%a, %b, %i), kind=kOutput, calls=%fused
}
"""
    cost = flops.hlo_op_costs(text)["fusion.7"]
    assert cost.flops == 2 * 8 * 64 * 64
    # one 64x64 layer of the stacked weight read, the 8x64 operand lives
    # on chip (S(1)), and one 8x64 slice written in place, s32 index 4 B
    assert cost.bytes == 2 * 64 * 64 + 4 + 2 * 8 * 64


def test_peaks_of_the_chip_and_unknown_kind_raises():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")


@pytest.mark.parametrize("cell,fpt", [
    ("qwen2.5-3b-lora-fcdp-4k", 13_572_866_048),
    ("qwen2.5-3b-lora-zero3-4k", 13_572_866_048),
    ("granite-3-8b-full-fcdp-4x4k", 11_576_352_768),
])
def test_model_flops_of_the_cells(cell, fpt):
    """mfu's FLOPs per token of each cell, by its configuration's family."""
    from benchmarks.chip import harness
    c = harness.load_cell(cell)
    got = c.family.model_flops_per_token(c.config, int(c.mix["seq_len"]),
                                         c.peft)
    assert got == fpt

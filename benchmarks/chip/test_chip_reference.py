"""The plain float32 reference against the program's own train step, on
the CPU at smoke size: LoRA under fcdp on one device, and a full
fine-tune under fcdp on pod=2 x data=2. They must agree on the losses,
the first gradient of every trainable leaf and each leaf's change over
three steps; the reference computed with fp8 matmuls (the control) must
not."""
import jax
import pytest

from benchmarks.chip import correct, reference
from benchmarks.chip.conftest import SMOKE_LIMITS

SEED = 2**31 + 77          # seeds run past 32 signed bits


@pytest.mark.parametrize("cell", ["qwen-smoke-lora", "granite-smoke-full"])
def test_reference_agrees_with_program_and_control_does_not(
        smoke_root, cpu_run, cell):
    harness = cpu_run
    c = harness.load_cell(cell, smoke_root)
    devices = jax.devices()[:c.chips]
    st = harness.build(c, SEED, devices)
    losses = []
    for step in range(harness.WARMUP_STEPS):
        losses.append(harness.drive(st, step))
        if step == 0:
            g1 = harness.first_grad_norms(st)
    prog = correct.Readings(losses, g1, harness.change_norms(st, c, SEED))
    harness.free_state(st)

    ref = reference.train(c.family, c.config, c.job, c.mix, SEED, 3, devices)
    assert set(ref.change_norms) == set(g1)
    got = correct.numbers(prog, ref)
    for name, limit in SMOKE_LIMITS.items():
        assert got[name] <= limit, (name, got)

    ctl = reference.train(c.family, c.config, c.job, c.mix, SEED, 3, devices,
                          precision="fp8")
    control = correct.numbers(
        correct.Readings(ctl.losses, ctl.grad_norms[0], ctl.change_norms), ref)
    assert any(control[n] > lim for n, lim in SMOKE_LIMITS.items()), control


def test_reference_draws_the_programs_weights(smoke_root, cpu_run):
    """The reference's own initialisation from the seed gives, leaf by
    leaf, the program's initial weights (compared here, in the test
    only; the benchmark never hands the program's weights over)."""
    import numpy as np
    harness = cpu_run
    c = harness.load_cell("qwen-smoke-lora", smoke_root)
    st = harness.build(c, SEED, jax.devices()[:1])
    specs = c.family.param_specs(c.config, c.peft)
    keys = reference.leaf_keys(SEED, len(specs))
    b = st.bundle
    labels = [d.label for d in b.def_leaves]
    assert labels == [s.path for s in specs]
    params = b.merge(st.train_p, st.frozen_p)
    for leaf, k, s in zip(jax.tree.leaves(params), keys, specs):
        np.testing.assert_array_equal(
            np.asarray(leaf, np.float32),
            np.asarray(reference.init_leaf(k, s)), err_msg=s.path)

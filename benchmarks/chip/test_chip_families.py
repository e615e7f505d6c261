"""The family seam: the reference's layer-by-layer driver over several
layer groups of different leaf sets gives the loss and gradients of the
same model written whole, and a configuration must name a family that
has a module."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.chip import reference as ref
from benchmarks.chip.conftest import add_smoke_cells

D, V, B, S, EPS = 16, 40, 2, 64, 1e-6
# two groups: a GLU MLP of width 24 in two layers, then one of width 40
# in three layers with a frozen bias leaf of its own
GROUPS = (("blocks.a", 2, 24, False), ("blocks.b", 3, 40, True))


def _specs():
    specs = [ref.LeafSpec("embed", (V, D), "embed", True),
             ref.LeafSpec("final_norm", (D,), "ones", True),
             ref.LeafSpec("head", (D, V), "normal", True)]
    for prefix, L, F, bias in GROUPS:
        specs += [ref.LeafSpec(f"{prefix}.norm", (L, D), "ones", True),
                  ref.LeafSpec(f"{prefix}.w_gate", (L, D, F), "normal", True),
                  ref.LeafSpec(f"{prefix}.w_in", (L, D, F), "normal", True),
                  ref.LeafSpec(f"{prefix}.w_out", (L, F, D), "normal", True)]
        if bias:
            specs.append(ref.LeafSpec(f"{prefix}.bias", (L, D), "normal",
                                      False))
    return sorted(specs, key=lambda s: s.path.split("."))


def _layer(x, p, ein):
    h = ref.rms_norm(x, p["norm"], EPS)
    z = jax.nn.silu(ein("bsd,df->bsf", h, p["w_gate"])) * ein(
        "bsd,df->bsf", h, p["w_in"])
    y = x + ein("bsf,fd->bsd", z, p["w_out"])
    return y + p["bias"] if "bias" in p else y


def _whole_loss(master, ids, labels, mask, ein):
    """The same model in one piece: every layer of both groups, then the
    loss, differentiated at once."""
    w = {k: ref.to_bf16(v.astype(jnp.float32)) for k, v in master.items()}
    # the driver takes the table's gradient as the lookup's own transpose,
    # a scatter-add of the f32 gradient, where JAX would round it to bf16
    # through reduce_precision as it does for every other leaf
    e = master["embed"]
    x = jnp.take(e + jax.lax.stop_gradient(w["embed"] - e), ids, axis=0)
    for prefix, L, _, _ in GROUPS:
        names = [k[len(prefix) + 1:] for k in w if k.startswith(prefix + ".")]
        for l in range(L):
            x = _layer(x, {n: w[f"{prefix}.{n}"][l] for n in names}, ein)
    s, c = ref.loss_sums(x, w["final_norm"], w["head"], labels, mask, EPS,
                         ein)
    return s / c


def test_driver_over_two_groups_matches_the_whole_model():
    specs = _specs()
    keys = ref.leaf_keys(2**31 + 9, len(specs))
    master = {s.path: ref.init_leaf(k, s).astype(
        jnp.float32 if s.trainable else jnp.bfloat16)
        for k, s in zip(keys, specs)}
    trainable = frozenset(s.path for s in specs if s.trainable)
    model = ref.Model(groups=[ref.Group(p, L, _layer) for p, L, _, _ in GROUPS],
                      embed="embed", final_norm="final_norm", head="head",
                      norm_eps=EPS)
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    mask = jnp.asarray(rng.random((B, S)) < 0.8)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    row = NamedSharding(mesh, P("x"))
    ein = ref._einsum("f32")

    with jax.default_matmul_precision("highest"):
        loss, grads = ref.layerwise(model, specs, trainable, ein, mesh, row)(
            master, jax.device_put(ids, row), jax.device_put(labels, row),
            jax.device_put(mask, row))

        def whole(tp):
            return _whole_loss({**master, **tp}, ids, labels, mask, ein)
        want_loss, want = jax.value_and_grad(whole)(
            {k: master[k] for k in trainable})

    assert set(grads) == trainable and "blocks.b.bias" not in grads
    # f32 throughout: the two orders of the same sums differ by round-off
    # of a few ulps (1.2e-7 each), far under these tolerances
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    for k in sorted(trainable):
        scale = float(jnp.max(jnp.abs(want[k])))
        np.testing.assert_allclose(np.asarray(grads[k]), np.asarray(want[k]),
                                   rtol=0, atol=1e-5 * scale, err_msg=k)

    stray = specs + [ref.LeafSpec("blocks.c.w", (1, D, D), "normal", True)]
    with pytest.raises(ValueError, match="blocks.c.w"):
        ref.group_leaves(model, stray)


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_configuration_without_a_family_module_is_refused(tmp_path, family):
    from benchmarks.chip import harness
    root = add_smoke_cells(tmp_path)
    conf = root / "benchmarks/chip/configs/qwen-smoke.json"
    cfg = json.loads(conf.read_text())
    cfg.pop("family")
    if family:
        cfg["family"] = family
    conf.write_text(json.dumps(cfg))
    looked_for = re.escape(str(root / "benchmarks/chip/families"))
    if family:
        looked_for += re.escape(f"/{family}.py")
    with pytest.raises((ValueError, FileNotFoundError),
                       match=re.escape(str(conf)) + ".*" + looked_for):
        harness.load_cell("qwen-smoke-lora", root)

"""Strategy-layer tests: golden parity of the four built-in strategies
against the seed's storage/gather schedule, registry behaviour, and the
layer-ahead prefetch scheduler (numerical equivalence + comm structure).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ModelConfig, OptimizerConfig, RunConfig,
                                ShapeCell, SystemConfig)
from repro.core.engine import StepBundle
from repro.core.partition import ParamDef
from repro.core.strategy import (DEFAULT_STRATEGY, GatherPlan,
                                 ShardingStrategy, get_strategy,
                                 register_strategy, resolve_strategy,
                                 strategy_names)

DENSE = ModelConfig(name="t-dense", family="dense", num_layers=2, d_model=64,
                    num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                    qkv_bias=True)
CELL = ShapeCell("t", "train", 64, 8)

# a stacked 2D weight with an fsdp dim, as every block weight has
WDEF = ParamDef((2, 64, 128), ("stack", "fsdp", None))
WDEF_FROZEN = ParamDef((2, 64, 128), ("stack", "fsdp", None), frozen=True)
WDEF_TP = ParamDef((64, 128), ("fsdp", "tp"))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_contents():
    names = strategy_names()
    for name in ("zero3", "zeropp", "fcdp", "mics"):
        assert name in names
        assert get_strategy(name).name == name
    assert DEFAULT_STRATEGY in names
    # singletons: SystemConfig.mode resolves to the same object each time
    assert get_strategy("fcdp") is get_strategy("fcdp")
    assert resolve_strategy(get_strategy("zero3")) is get_strategy("zero3")
    with pytest.raises(ValueError, match="unknown system mode"):
        get_strategy("zero17")


def test_register_custom_strategy():
    class Hierarchical(ShardingStrategy):
        name = "test_hier"
        cache_placement = "device"
    try:
        register_strategy(Hierarchical)
        assert get_strategy("test_hier").cache_placement == "device"
        # a full StepBundle builds against the new mode
        run = RunConfig(model=DENSE, shape=CELL,
                        system=SystemConfig(mode="test_hier",
                                            min_shard_size=8))
        from repro.launch.mesh import make_mesh
        b = StepBundle(run, make_mesh((2, 2, 2), ("pod", "data", "model")))
        assert b.strategy.name == "test_hier"
    finally:
        from repro.core import strategy as strat_mod
        strat_mod._REGISTRY.pop("test_hier", None)


# ---------------------------------------------------------------------------
# Golden parity: each strategy reproduces the seed's storage_spec and
# GatherPlan (fsdp dim, inter/intra axes, cache boundary) on both meshes.
# ---------------------------------------------------------------------------

# (mode, frozen) -> expected (spec entry, inter_axes, intra_axes,
# cache_after) on the multi-pod ('pod','data','model') mesh.
# Full sharding tiles INTRA-major (('data','pod'), pod last): the
# two-stage gather runs stage 1 (pod) then stage 2 (data), so data-major
# storage is what makes the staged reconstruction land blocks in true
# global order -- required for per-tensor mixed sharding, where a
# two-stage-gathered leaf contracts against single-stage (mics/hier/
# frozen) leaves and both must agree on the gathered basis.
GOLDEN_MULTIPOD = {
    ("zero3", False): (("data", "pod"), ("pod",), ("data",), 1),
    ("zeropp", False): (("data", "pod"), ("pod",), ("data",), 1),
    ("fcdp", False): (("data", "pod"), ("pod",), ("data",), 1),
    ("mics", False): ("data", (), ("data",), 2),
    # hier: params take the MiCS (pod-replicated) layout; only the
    # OPTIMIZER state widens to ('data','pod') -- see test_hier_opt_spec
    ("hier", False): ("data", (), ("data",), 2),
    # frozen: FCDP-Comm cached layout applies in fcdp only
    ("zero3", True): (("data", "pod"), ("pod",), ("data",), 1),
    ("zeropp", True): (("data", "pod"), ("pod",), ("data",), 1),
    ("fcdp", True): ("data", (), ("data",), 2),
    ("mics", True): ("data", (), ("data",), 2),
    ("hier", True): ("data", (), ("data",), 2),
}


@pytest.mark.parametrize("mode", ["zero3", "zeropp", "fcdp", "mics", "hier"])
@pytest.mark.parametrize("frozen", [False, True])
def test_golden_parity_multipod(mesh3, mode, frozen):
    strat = get_strategy(mode)
    pdef = WDEF_FROZEN if frozen else WDEF
    spec_entry, inter, intra, cache_after = GOLDEN_MULTIPOD[(mode, frozen)]
    spec = strat.storage_spec(pdef, mesh3)
    assert spec == P(None, spec_entry, None), (mode, frozen, spec)
    plan = strat.gather_plan(pdef, mesh3)
    assert plan.is_gathered
    assert plan.fsdp_dim == 0          # stack dim consumed by scan
    assert plan.inter_axes == inter
    assert plan.intra_axes == intra
    assert plan.cache_after == cache_after
    assert plan.frozen == frozen


@pytest.mark.parametrize("mode", ["zero3", "zeropp", "fcdp", "mics", "hier"])
def test_golden_parity_singlepod(mesh2, mode):
    """No pod axis: every strategy collapses to ('data',) storage with an
    empty stage 1 and the cache boundary after the full gather."""
    strat = get_strategy(mode)
    spec = strat.storage_spec(WDEF, mesh2)
    assert spec == P(None, "data", None), (mode, spec)
    plan = strat.gather_plan(WDEF, mesh2)
    assert plan.inter_axes == ()
    assert plan.intra_axes == ("data",)
    assert plan.cache_after == 2
    assert not plan.prefetchable


def test_golden_parity_tp_dim(mesh3):
    for mode in ("zero3", "fcdp"):
        spec = get_strategy(mode).storage_spec(WDEF_TP, mesh3)
        assert spec == P(("data", "pod"), "model"), (mode, spec)


def test_cache_placement_per_mode():
    assert get_strategy("zero3").cache_placement == "regather"
    assert get_strategy("zeropp").cache_placement == "device"
    assert get_strategy("fcdp").cache_placement == "host"
    assert get_strategy("mics").cache_placement == "regather"
    assert get_strategy("hier").cache_placement == "regather"


def test_device_cache_fraction_gating():
    # FCDP-Cache's tau fraction only applies under fcdp
    assert get_strategy("fcdp").device_cache_groups(8, 0.5) == 4
    for mode in ("zero3", "zeropp", "mics", "hier"):
        assert get_strategy(mode).device_cache_groups(8, 0.5) == 0


def test_hier_opt_spec(mesh3, mesh2):
    """hier shards optimizer state wider than params: storage is the
    MiCS (pod-replicated) layout, opt state goes over the full fsdp
    product with the storage axes MAJOR in the tiling order (so the
    widening reduce-scatter lands on the device's opt slice)."""
    hier = get_strategy("hier")
    assert hier.storage_spec(WDEF, mesh3) == P(None, "data", None)
    assert hier.opt_spec(WDEF, mesh3) == P(None, ("data", "pod"), None)
    # no pod axis: opt layout collapses to the param layout
    assert hier.opt_spec(WDEF, mesh2) == hier.storage_spec(WDEF, mesh2)
    # every other built-in keeps opt state at the (full-scope) param layout
    import dataclasses
    for mode in ("zero3", "zeropp", "fcdp", "mics"):
        s = get_strategy(mode)
        assert s.opt_spec(WDEF, mesh3) == s.storage_spec(
            dataclasses.replace(WDEF, fsdp_scope="full"), mesh3)


def test_legacy_module_level_helpers_delegate(mesh3):
    """The partition/fcdp module-level helpers accept mode names and
    produce the strategy's result (back-compat seam)."""
    from repro.core.fcdp import make_gather_plan
    from repro.core.partition import storage_spec
    for mode in strategy_names():
        strat = get_strategy(mode)
        assert storage_spec(WDEF, mesh3, mode) == strat.storage_spec(
            WDEF, mesh3)
        assert make_gather_plan(WDEF, mesh3, mode) == strat.gather_plan(
            WDEF, mesh3)


# ---------------------------------------------------------------------------
# Prefetch scheduler
# ---------------------------------------------------------------------------

def make_bundle(mesh, mode=DEFAULT_STRATEGY, **sys_kw):
    sysd = dict(mode=mode, min_shard_size=8)
    sysd.update(sys_kw)
    run = RunConfig(model=DENSE, shape=CELL, system=SystemConfig(**sysd),
                    optimizer=OptimizerConfig(total_steps=8, warmup_steps=2,
                                              lr=1e-3))
    return StepBundle(run, mesh)


def make_batch(seed=0):
    rng = np.random.default_rng(seed)
    b = {"ids": jnp.asarray(
            rng.integers(1, DENSE.vocab_size,
                         (CELL.global_batch, CELL.seq_len)), jnp.int32),
         "labels": jnp.asarray(
            rng.integers(1, DENSE.vocab_size,
                         (CELL.global_batch, CELL.seq_len)), jnp.int32)}
    b["mask"] = jnp.ones_like(b["labels"], bool)
    return b


def run_one_step(bundle):
    from repro.optim.adamw import init_opt_state
    params = bundle.init_all_params(seed=0)
    tp, fp = bundle.split(params)
    opt = jax.jit(functools.partial(
        init_opt_state, sys=bundle.run.system))(tp)
    step = bundle.make_train_step()
    tp, opt, m = step(tp, fp, opt, make_batch())
    return ({k: float(v) for k, v in m.items()},
            [np.asarray(x, np.float32) for x in tp])


def test_prefetch_gating():
    """Strategy x mesh gating: prefetch needs a pod axis, a willing
    strategy, and the config flag."""
    sys_on = SystemConfig(prefetch_depth=1)
    sys_off = SystemConfig(prefetch_depth=0)

    class M3:
        axis_names = ("pod", "data", "model")

    class M2:
        axis_names = ("data", "model")

    for mode in ("zero3", "zeropp", "fcdp"):
        assert get_strategy(mode).prefetch_active(sys_on, M3())
        assert not get_strategy(mode).prefetch_active(sys_off, M3())
        assert not get_strategy(mode).prefetch_active(sys_on, M2())
    assert not get_strategy("mics").prefetch_active(sys_on, M3())


@pytest.mark.parametrize("mode", ["zero3", "fcdp"])
def test_prefetch_numerical_equivalence(mesh3, mode):
    """The layer-ahead schedule must not change the math: one training
    step with prefetch on/off produces identical loss, grad norm, and
    updated parameters (tolerances absorb reduction-order noise)."""
    m_off, p_off = run_one_step(make_bundle(mesh3, mode=mode,
                                            prefetch_depth=0))
    m_on, p_on = run_one_step(make_bundle(mesh3, mode=mode,
                                          prefetch_depth=1))
    np.testing.assert_allclose(m_on["loss"], m_off["loss"], rtol=1e-4)
    np.testing.assert_allclose(m_on["grad_norm"], m_off["grad_norm"],
                               rtol=1e-3)
    for a, b in zip(p_off, p_on):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3)


def test_prefetch_depth_k_equivalence(mesh3):
    """Deepening the ring buffer (k=2, k > num layer groups) must not
    change the math either: train loss and updated params match the
    depth-1 schedule."""
    m_1, p_1 = run_one_step(make_bundle(mesh3, mode="fcdp",
                                        prefetch_depth=1))
    for depth in (2, 7):          # 7 > num_layers: the scheduler clamps
        m_k, p_k = run_one_step(make_bundle(mesh3, mode="fcdp",
                                            prefetch_depth=depth))
        np.testing.assert_allclose(m_k["loss"], m_1["loss"], rtol=1e-4)
        np.testing.assert_allclose(m_k["grad_norm"], m_1["grad_norm"],
                                   rtol=1e-3)
        for a, b in zip(p_1, p_k):
            np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3)


def test_hier_step_matches_zero3(mesh3):
    """Golden run for the hier strategy: one training step produces the
    same loss/grad norm/updated params as zero3 (identical math, only
    the storage/opt layouts and reduce schedule differ)."""
    m_z, p_z = run_one_step(make_bundle(mesh3, mode="zero3"))
    m_h, p_h = run_one_step(make_bundle(mesh3, mode="hier"))
    np.testing.assert_allclose(m_h["loss"], m_z["loss"], rtol=1e-4)
    np.testing.assert_allclose(m_h["grad_norm"], m_z["grad_norm"],
                               rtol=1e-3)
    for a, b in zip(p_z, p_h):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3)


def _collect(bundle):
    from repro.launch.roofline import collect_collectives
    step = bundle.make_train_step()
    closed = step.trace(*bundle.train_input_sds()).jaxpr
    sizes = {a: bundle.mi.size(a) for a in bundle.mi.axis_names}
    return collect_collectives(closed, sizes)


def test_prefetch_comm_structure(mesh3):
    """fcdp already re-runs only stage 2 in the backward, so prefetch
    must leave its total DCN all-gather volume unchanged at EVERY ring
    depth (the schedule moves bytes earlier, it does not add any); the
    gradient reduce-scatter volume is identical too. MiCS is untouched
    entirely."""
    fc_off = _collect(make_bundle(mesh3, mode="fcdp",
                              prefetch_depth=0))
    for depth in (1, 2):
        fc_on = _collect(make_bundle(mesh3, mode="fcdp",
                                     prefetch_depth=depth))
        np.testing.assert_allclose(
            fc_on.by_op_axis.get("all_gather/pod", 0),
            fc_off.by_op_axis.get("all_gather/pod", 0), rtol=1e-6)
        np.testing.assert_allclose(
            fc_on.by_op.get("psum_scatter", 0),
            fc_off.by_op.get("psum_scatter", 0), rtol=1e-6)

    mi_off = _collect(make_bundle(mesh3, mode="mics", prefetch_depth=0))
    mi_on = _collect(make_bundle(mesh3, mode="mics", prefetch_depth=1))
    assert mi_on.by_op_axis.get("all_gather/pod", 0) == 0
    np.testing.assert_allclose(mi_on.dcn_bytes, mi_off.dcn_bytes, rtol=1e-6)
    np.testing.assert_allclose(mi_on.ici_bytes, mi_off.ici_bytes, rtol=1e-6)


def test_prefetch_roofline_overlap_visibility():
    """The roofline model credits prefetch with the stage-1 DCN AG
    overlap and leaves non-prefetch reports unchanged."""
    from repro.launch.roofline import CollectiveStats, roofline_report
    stats = CollectiveStats()
    stats.add("all_gather", "pod", 4e9, is_dcn=True)
    stats.add("all_gather", "data", 8e9, is_dcn=False)
    rep_off = roofline_report(1e15, 1e12, stats, DENSE, CELL, 8,
                              prefetch=False)
    rep_on = roofline_report(1e15, 1e12, stats, DENSE, CELL, 8,
                             prefetch=True)
    assert rep_off["prefetch"]["overlapped_dcn_bytes_per_chip"] == 0
    assert rep_off["prefetch"]["collective_exposed_s"] == pytest.approx(
        rep_off["collective_s"])
    assert rep_on["prefetch"]["overlapped_dcn_bytes_per_chip"] == 4e9
    assert (rep_on["prefetch"]["collective_exposed_s"]
            < rep_on["collective_s"])
    # overlap is capped by the compute term
    assert rep_on["prefetch"]["overlapped_s"] <= rep_on["compute_s"] + 1e-12


def test_remat_policy_host_tier_holds_matrices():
    """A host-placed cache offloads matrices to pinned host memory and
    keeps vectors (norm scales, biases) in HBM: the TPU compiler refuses
    the one-row host updates a stacked vector cache would need."""
    from jax.ad_checkpoint import Offloadable, Recompute, Saveable
    from jax._src.ad_checkpoint import name_p

    from repro.core.fcdp import make_remat_policy
    pol = make_remat_policy("host")
    mat = jax.core.ShapedArray((2048, 1024), jnp.bfloat16)
    vec = jax.core.ShapedArray((2048,), jnp.bfloat16)
    assert isinstance(pol(name_p, mat, name="fcdp_cache:host"), Offloadable)
    assert pol(name_p, vec, name="fcdp_cache:host") is Saveable
    assert pol(name_p, mat, name="fcdp_cache:device") is Saveable
    assert pol(name_p, mat, name="fcdp_cache:regather") is Recompute
    assert make_remat_policy("host", host_offload=False)(
        name_p, mat, name="fcdp_cache:host") is Saveable

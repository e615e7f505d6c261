"""Causal self-attention on the fused flash-attention kernel (JAX's
bundled Pallas kernel, forward and backward), run in the TPU interpreter
on the CPU: its output and gradients against the chunked jnp path, alone
and inside the train step's checked ``shard_map``; which calls of
``attention_block`` take it; and the counter of the paths taken."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import SystemConfig
from repro.configs.registry import get_smoke_config
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import attention as attn
from repro.models.common import MeshInfo
from repro.runtime import lowerings

TOL = 2.0 ** -7          # bf16's machine epsilon, relative to the norm
HD = 128


def _rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _qkv(B=1, S=512, H=4, KV=2, seed=0):
    """bf16 q and kv-expanded k, v of GQA 4 over 2, and a cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, do = (jax.random.normal(k, (B, S, H, HD), jnp.bfloat16)
             for k in (ks[0], ks[3]))
    k, v = (attn._expand_kv(jax.random.normal(k, (B, S, KV, HD),
                                              jnp.bfloat16), H // KV)
            for k in (ks[1], ks[2]))
    return q, k, v, do


def _kernel(q, k, v):
    return ops.causal_attention_train(q, k, v, softmax_scale=HD ** -0.5,
                                      interpret=True)


def _vjp(fn, q, k, v, do):
    out, pull = jax.vjp(fn, q, k, v)
    return (out, *pull(do))


@pytest.mark.parametrize("sharded", [False, True], ids=["plain", "shard_map"])
def test_kernel_matches_chunked_path(sharded):
    """Output and dq/dk/dv of the kernel against the chunked path within
    bf16's epsilon; inside a checked shard_map over two devices the
    kernel's results carry the batch axis's varying type."""
    q, k, v, do = _qkv(B=2 if sharded else 1)
    want = _vjp(attn.chunked_causal_attention, q, k, v, do)
    if sharded:
        mesh = make_mesh((2,), ("data",), jax.devices()[:2])
        got = jax.jit(jax.shard_map(
            lambda *a: _vjp(_kernel, *a), mesh=mesh,
            in_specs=(P("data"),) * 4, out_specs=(P("data"),) * 4))(
                q, k, v, do)
    else:
        got = _vjp(_kernel, q, k, v, do)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert g.dtype == w.dtype == jnp.bfloat16, name
        assert _rel(g, w) < TOL, (name, _rel(g, w))


def test_attention_block_on_kernel_matches_chunked():
    """The whole attention sublayer on a data=2 x model=2 mesh (two
    local heads per rank, GQA expanded by ``slice_expand_kv``): the
    kernel path's output and weight gradients against 'jnp'."""
    cfg = dataclasses.replace(get_smoke_config("qwen2.5-3b"), head_dim=HD)
    mesh = make_mesh((2, 2), ("data", "model"), jax.devices()[:4])
    mi = MeshInfo.from_mesh(mesh)
    B, S, D = 2, 256, cfg.d_model
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (B, S, D), jnp.bfloat16)
    wq, wo = (0.1 * jax.random.normal(k, s, jnp.bfloat16) for k, s in
              ((ks[1], (D, 4 * HD)), (ks[2], (4 * HD, D))))
    wk, wv = (0.1 * jax.random.normal(k, (D, 2 * HD), jnp.bfloat16)
              for k in ks[3:5])
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))

    def grads(impl):
        def loss(x, wq, wk, wv, wo, pos):
            y, _ = attn.attention_block(x, wq, wk, wv, wo, None, None, None,
                                        cfg, mi, pos, attn_impl=impl)
            return jax.lax.psum(jnp.sum(y.astype(jnp.float32) ** 2),
                                "data")

        def body(*a):
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*a)
        w_col, w_row = P(None, "model"), P("model", None)
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("data"), w_col, P(), P(), w_row, P("data")),
            out_specs=(P(), (P("data"), w_col, P(), P(), w_row))))(
                x, wq, wk, wv, wo, pos)

    (l_k, g_k), (l_c, g_c) = grads("pallas_interpret"), grads("jnp")
    assert abs(float(l_k) - float(l_c)) / float(l_c) < TOL
    for name, a, b in zip(("x", "wq", "wk", "wv", "wo"), g_k, g_c):
        assert _rel(a, b) < TOL, (name, _rel(a, b))


# ---------------------------------------------------------------------------
# Which calls take the kernel
# ---------------------------------------------------------------------------

def _case(S=256, impl="pallas", platform="tpu", causal=True, cache=None):
    return dict(S=S, impl=impl, platform=platform, causal=causal,
                cache=cache)


PATHS = {
    "kernel": ("kernel", _case()),
    "kernel_interpret_on_cpu": ("kernel", _case(impl="pallas_interpret",
                                                platform="cpu")),
    "jnp": ("chunked", _case(impl="jnp")),
    "cpu_mesh": ("chunked", _case(platform="cpu")),
    "non_causal": ("chunked", _case(causal=False)),
    "untiled_rows": ("chunked", _case(S=200)),
    "kv_cache": ("chunked", _case(cache="kv_cache")),
    "paged_kv": ("chunked", _case(cache="paged_kv")),
}


@pytest.mark.parametrize("name", sorted(PATHS))
def test_attention_path_selection(name):
    """Traced, not run: each call records its path once, and only the
    kernel path holds a pallas_call."""
    want, c = PATHS[name]
    cfg = dataclasses.replace(get_smoke_config("qwen2.5-3b"), head_dim=HD)
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    mi = dataclasses.replace(MeshInfo.from_mesh(mesh), platform=c["platform"])
    B, S, D = 1, c["S"], cfg.d_model
    f32 = jax.ShapeDtypeStruct
    x = f32((B, S, D), jnp.bfloat16)
    wq, wo = f32((D, 4 * HD), jnp.bfloat16), f32((4 * HD, D), jnp.bfloat16)
    wk = f32((D, 2 * HD), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    kw = {}
    if c["cache"] == "kv_cache":
        kw["kv_cache"] = (jnp.zeros((B, 512, 2, HD), jnp.bfloat16),
                          jnp.zeros((B, 512, 2, HD), jnp.bfloat16),
                          jnp.int32(0))
    elif c["cache"] == "paged_kv":
        kw["paged_kv"] = (jnp.zeros((8, 64, 2, HD), jnp.bfloat16),
                          jnp.zeros((8, 64, 2, HD), jnp.bfloat16),
                          jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4) + 1)

    def body(x, wq, wk, wv, wo):
        y, _ = attn.attention_block(x, wq, wk, wv, wo, None, None, None,
                                    cfg, mi, pos, attn_impl=c["impl"],
                                    causal=c["causal"], **kw)
        return y

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(),) * 5,
                       out_specs=P())
    before = lowerings.attention_paths()
    jaxpr = str(jax.make_jaxpr(fn)(x, wq, wk, wk, wo))
    after = lowerings.attention_paths()
    assert {p: after[p] - before[p] for p in after} == {
        p: int(p == want) for p in lowerings.ATTENTION_PATHS}
    assert ("pallas_call" in jaxpr) == (want == "kernel")


def test_attn_impl_defaults_to_the_kernel():
    assert SystemConfig().attn_impl == "pallas"


@pytest.mark.parametrize("paths,want", [
    ({"kernel": 3, "chunked": 0}, 1.0), ({"kernel": 3, "chunked": 1}, 0.75),
    ({"kernel": 0, "chunked": 2}, 0.0), (None, None)])
def test_kernel_share_reads_the_counter(monkeypatch, paths, want):
    """The benchmark's ``attention.kernel_share`` reads kernel / (kernel
    + chunked) from the run's counters, and nothing without them."""
    import importlib.util
    from pathlib import Path

    from benchmarks.chip import scoped
    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "metrics" / "attention.kernel_share.py")
    spec = importlib.util.spec_from_file_location("kernel_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    counters = {} if paths is None else {"attention_paths": paths}
    monkeypatch.setattr(scoped, "of", lambda run: types.SimpleNamespace(
        counters=counters))
    assert mod.read(object()) == want


# ---------------------------------------------------------------------------
# The projections with adapters, and the bundled kernel's private rules
# ---------------------------------------------------------------------------

def _plain_f32_project(x, w, bias, lora, name, scale):
    """The reference's projection: every product and sum in f32 at
    ``highest``, rounded once to x's dtype."""
    f, hi = (lambda t: t.astype(jnp.float32)), jax.lax.Precision.HIGHEST
    y = jnp.dot(f(x), f(w), precision=hi)
    a = lora.get(f"{name}_lora_a") if lora else None
    if a is not None:
        y = y + jnp.dot(jnp.dot(f(x), f(a), precision=hi),
                        f(lora[f"{name}_lora_b"]), precision=hi) * scale
    return (y if bias is None else y + f(bias)).astype(x.dtype)


def _adapted_block_inputs(R=8, seed=2):
    """Smoke-width attention weights with QKV bias and rank-R adapters
    on q/k/v/o whose B is lr-sized, far below the bf16 step of the
    projections."""
    cfg = get_smoke_config("qwen2.5-3b")
    hd, H, KV, D = (cfg.resolved_head_dim(), cfg.num_heads,
                    cfg.num_kv_heads, cfg.d_model)
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def draw(shape, scale):
        return (scale * jax.random.normal(next(ks), shape)).astype(
            jnp.bfloat16)
    x = draw((1, 128, D), 1.0)
    shapes = dict(wq=(D, H * hd), wk=(D, KV * hd), wv=(D, KV * hd),
                  wo=(H * hd, D))
    w = {n: draw(s, s[0] ** -0.5) for n, s in shapes.items()}
    bias = {n: draw((shapes[n][1],), 0.1) for n in ("wq", "wk", "wv")}
    lora = {}
    for n, (din, dout) in shapes.items():
        lora[f"{n}_lora_a"] = draw((din, R), din ** -0.5)
        lora[f"{n}_lora_b"] = (1e-4 * jnp.sign(jax.random.normal(
            next(ks), (R, dout)))).astype(jnp.bfloat16)
    return cfg, x, w, bias, lora


def test_projection_with_adapter_rounds_once():
    """A projection with an adapter equals the plain f32 sum rounded once
    to within one bf16 step everywhere and exactly nearly everywhere,
    and keeps the adapter's sub-step term where that sum does."""
    cfg, x, w, bias, lora = _adapted_block_inputs()
    got, want, bare = (np.asarray(f(x, w["wq"], bias["wq"], lo, "wq", 2.0),
                                  np.float32)
                       for f, lo in ((attn.project, lora),
                                     (_plain_f32_project, lora),
                                     (attn.project, None)))
    step = np.abs(want) * 2.0 ** -7 + 1e-30
    assert np.all(np.abs(got - want) <= step)
    assert np.mean(got == want) > 0.99
    assert np.any(got != bare) and np.array_equal(got != bare, want != bare)


def test_attention_block_with_adapters_matches_f32_projections(monkeypatch):
    """The sublayer with QKV bias and adapters on q/k/v/o against the
    same sublayer on plain f32 projections rounded once: as close as a
    rare rounding flip allows (an adapter's term added to an already
    rounded projection reads about 20 times further off here)."""
    cfg, x, w, bias, lora = _adapted_block_inputs()
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    mi = MeshInfo.from_mesh(mesh)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    def block(x, w, bias, lora):
        y, _ = attn.attention_block(
            x, w["wq"], w["wk"], w["wv"], w["wo"], bias["wq"], bias["wk"],
            bias["wv"], cfg, mi, pos, attn_impl="jnp", lora=lora,
            lora_alpha=2.0)
        return y

    def run():
        return np.asarray(jax.jit(jax.shard_map(
            block, mesh=mesh, in_specs=P(), out_specs=P()))(
                x, w, bias, lora), np.float32)
    got = run()
    monkeypatch.setattr(attn, "project", _plain_f32_project)
    want = run()
    assert _rel(got, want) < 2.0 ** -9, _rel(got, want)


def test_private_flash_rules_keep_their_parameters():
    """``kernels/ops.py`` calls the bundled kernel's forward and backward
    rules by these keywords; a JAX that renames or reorders them fails
    here and not on the chip."""
    import inspect

    from repro import compat
    assert list(inspect.signature(compat.flash_attention_fwd).parameters) \
        == ["q", "k", "v", "ab", "segment_ids", "save_residuals", "causal",
            "sm_scale", "block_sizes", "debug"]
    assert list(inspect.signature(compat.flash_attention_bwd).parameters) \
        == ["save_residuals", "causal", "sm_scale", "block_sizes", "debug",
            "residuals", "do"]
    with compat.check_vma(False):
        assert not compat.check_vma.value

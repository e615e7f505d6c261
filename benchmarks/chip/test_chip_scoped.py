"""Device time by program scope (``scoped.py``): the metadata of a
compiled step, the reduction of a trace by scope and pass, the program's
host spans kept apart from the benchmark's window, the readers of the
new per-layer metrics, and the four-chip cell's files."""
import collections
import contextlib
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness, scoped, trace
from benchmarks.chip.conftest import REPO, add_smoke_cells

DATA = Path(__file__).resolve().parent / "testdata"
QWEN = "qwen2.5-3b-lora-fcdp-4k"
GRANITE = "granite-3-8b-full-fcdp-4x4k"
NEW = ("attention.device_ms", "mlp.device_ms", "loss.device_ms",
       "optimizer.device_ms", "stack.slice_ms", "remat.device_ms",
       "device.unscoped_share", "data.get_ms", "step.lowerings",
       "init.hbm_peak_gb", "setup.init_params_s")

HLO = """HloModule jit_step, entry_computation_layout={()->f32[]}

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="transpose(jvp(stack))/fcdp.gather1/reduce_scatter"}
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="fcdp.gather1/add"}
}

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.2 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/jvp(stack)/while/body/mlp/mul"}
  ROOT %ds.3 = f32[8]{0} dynamic-slice(%mul.2), metadata={op_name="jit(step)/jvp(stack)/while/body/dynamic_slice"}
}

ENTRY %main (train_params_0_: f32[8]) -> f32[] {
  %train_params_0_ = f32[8]{0} parameter(0), metadata={op_name="train_params[0]"}
  %convert.4 = f32[8]{0} convert(%train_params_0_)
  %fusion.5 = f32[8]{0} fusion(%convert.4), kind=kLoop, calls=%fused_computation.1
  %copy.6 = f32[8]{0} copy(%fusion.5)
  %all-reduce.7 = f32[8]{0} all-reduce(%copy.6), to_apply=%region_0
  %dot.8 = f32[] dot(%all-reduce.7, %all-reduce.7), metadata={op_name="jit(step)/transpose(jvp(loss))/dot_general"}
  ROOT %tuple.9 = (f32[]) tuple(%dot.8)
}
"""


def test_metadata_of_instructions_the_compiler_made():
    md = scoped.metadata(HLO)
    # a fusion takes the fullest op_name of what it fuses
    assert md.op_name["fusion.5"].endswith("while/body/mlp/mul")
    # a copy takes its operand's; a lowered reduce-scatter its reducer's
    assert md.op_name["copy.6"] == md.op_name["fusion.5"]
    assert scoped.scope_of(md.op_name["all-reduce.7"]) == "fcdp.gather1"
    assert scoped.pass_of(md.op_name["all-reduce.7"]) == "backward"
    # a parameter's label names no op: the convert takes the op_name of
    # the first user up the chain that has one of its own
    assert md.op_name["convert.4"] == md.op_name["dot.8"]
    assert md.collectives == {"all-reduce.7"}


def test_compiled_text_has_this_programs_scopes(tmp_path):
    """The persistent compile cache keys a program without its metadata:
    a step compiled without scopes comes back to the scoped program.
    ``compiled_text`` compiles it again, and the other way round."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def program(scoped_):
        def f(x):
            with (jax.named_scope("mlp") if scoped_
                  else contextlib.nullcontext()):
                return jnp.tanh(x @ x.T) * 3.0
        return jax.jit(f)

    x = jnp.ones((16, 16))
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        program(False).lower(x).compile()            # fills the cache
        cached = program(True).lower(x).compile().as_text()
        assert "/mlp/" not in cached                 # the other program's
        assert "/mlp/" in scoped.compiled_text(program(True), x)
        program(True).lower(jnp.ones((8, 8))).compile()
        assert "/mlp/" not in scoped.compiled_text(program(False),
                                                   jnp.ones((8, 8)))
    finally:
        jax.config.update("jax_enable_compilation_cache", was[0])
        jax.config.update("jax_compilation_cache_dir", was[1])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[2])
        cc.reset_cache()


@pytest.mark.parametrize("op_name,scope,pass_,remat", [
    ("jit(s)/transpose(jvp(stack))/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", "mlp", "backward", True),
    ("jit(s)/jvp(stack)/while/body/closed_call/attention/fcdp.gather2/"
     "all_gather", "fcdp.gather2", "forward", False),
    ("jit(s)/transpose(jvp(stack))/while/body/dynamic_update_slice",
     "stack", "backward", False),
    ("jit(s)/optimizer/mul", "optimizer", "other", False),
    ("jit(s)/jvp()/iota", None, "forward", False),
])
def test_scope_pass_and_remat_of_an_op_name(op_name, scope, pass_, remat):
    assert scoped.scope_of(op_name) == scope
    assert scoped.pass_of(op_name) == pass_
    assert scoped.is_remat(op_name) is remat


# ---------------------------------------------------------------------------
# The reduction, on a compiled CPU step and a trace made by hand
# ---------------------------------------------------------------------------

def _scoped_step_text():
    def layer(x, w):
        with jax.named_scope("attention"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("mlp"):
            return jnp.sin(h @ w.T)

    def loss(w, x):
        with jax.named_scope("stack"):
            y, _ = jax.lax.scan(
                lambda c, wi: (jax.checkpoint(layer)(c, wi), None), x, w)
        with jax.named_scope("loss"):
            return jnp.sum(y ** 2)

    w, x = jnp.ones((3, 16, 16)), jnp.ones((4, 16))
    return jax.jit(jax.grad(loss)).lower(w, x).compile().as_text()


def _pick(md, scope, pass_, remat=False):
    for name, on in sorted(md.op_name.items()):
        if (scoped.scope_of(on) == scope and scoped.pass_of(on) == pass_
                and scoped.is_remat(on) == remat):
            return name
    raise AssertionError(f"no {scope} {pass_} remat={remat} instruction")


def test_reduction_by_scope_pass_and_unscoped_share():
    md = scoped.metadata(_scoped_step_text())
    att_f = _pick(md, "attention", "forward")
    att_r = _pick(md, "attention", "backward", remat=True)
    mlp_b = _pick(md, "mlp", "backward")
    loss_f = _pick(md, "loss", "forward")
    md.op_name["ag.1"] = "jit(s)/jvp(stack)/while/body/fcdp.gather1/ag"
    host = ("%dynamic-slice-done.3 = bf16[2,8]{1,0:S(5)} "
            "dynamic-slice-done(%x)")

    def op(text, start, dur, on_async=False):
        return trace.parse_op(text, start, dur, on_async)
    ops = [op(f"%{att_f} = f32[4] fusion(%a)", 1000, 100),
           op(f"%{att_r} = f32[4] fusion(%a)", 1100, 30),
           op(f"%{mlp_b} = f32[4] fusion(%a)", 1130, 200),
           op(f"%{loss_f} = f32[4] fusion(%a)", 1330, 50),
           op("%made.9 = f32[4] fusion(%a)", 1380, 20),        # unscoped
           op(host, 1400, 40),                                 # host
           op("%while.2 = (f32[4]) while(%t), body=%b", 1000, 500),
           op(f"%{mlp_b} = f32[4] fusion(%a)", 5000, 70),      # outside
           op("%ag.1 = f32[8] all-gather-start(%a)", 1050, 300, True),
           op("%ag.1 = f32[8] all-gather-start(%a)", 1200, 300, True)]
    tr = trace.Trace({"TPU:0": ops}, {},
                     [("bench.input", 1000, 1010), ("bench.loss_read",
                                                    1490, 1500)])
    red = scoped.reduce_scopes(tr, md)
    s = red.scope["TPU:0"]
    assert s["attention"] == 130 and s["mlp"] == 200 and s["loss"] == 50
    assert s["stack"] == 0 and red.scoped
    assert red.remat["TPU:0"] == 30
    assert red.unscoped["TPU:0"] == 20
    p = red.passes["TPU:0"]
    assert p["attention forward"] == 100 and p["attention backward"] == 30
    assert p["mlp backward"] == 200 and p["host other"] == 40
    assert p["- other"] == 20
    assert red.gather1["TPU:0"] == 1500 - 1050       # a union, clipped
    base = trace.RunTrace(steps=2, red=trace.reduce_trace(tr), input_s=[],
                          matmul=None, compiled_bytes=None)
    busy = base.red.busy
    run = scoped.ScopedRun(base=base, red=red, spans=[], counters=None)
    assert run.scope_ms("attention") == pytest.approx(130 / 1e6 / 2)
    assert run.scope_ms("optimizer") is None
    assert busy["TPU:0"] == 440
    assert 100 * red.unscoped["TPU:0"] / busy["TPU:0"] == pytest.approx(
        100 * 20 / 440)
    t = run.table()
    assert t["by_scope_and_pass_ms"]["mlp backward"] == pytest.approx(1e-4)


# ---------------------------------------------------------------------------
# The recorded trace still reads as before
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    meta = json.loads((DATA / "trace_qwen_1chip.meta.json").read_text())
    return trace.Trace.from_json(str(DATA / "trace_qwen_1chip.json.gz")), meta


def test_recorded_trace_reads_as_before(recorded):
    """Every accepted reader's value and the breakdown of the recorded
    trace, as the parent commit read them, with the scope reduction run
    over the same trace."""
    from benchmarks.chip.flops import OpCost
    tr, meta = recorded
    names = frozenset(meta["matmul_names"])
    costs = {n: OpCost(*c) for n, c in meta["costs"].items()}
    scoped.reduce_scopes(tr, scoped.metadata(""))
    run = trace.RunTrace(
        steps=1, red=trace.reduce_trace(tr, names), input_s=[],
        matmul=trace.roofline(tr, costs, meta["module"], 197e12, 819e9),
        compiled_bytes=None)
    cell = harness.load_cell(QWEN)
    got = {m: harness.load_metric(cell, m).read(run) for m in (
        "device.idle_share", "host_cache.transfer_ms",
        "host_cache.exposed_ms", "matmul_roofline", "input.wait_ms",
        "step.compiled_hbm_gb")}
    assert got == pytest.approx({
        "device.idle_share": 99.36828763158438,
        "host_cache.transfer_ms": 31.39708,
        "host_cache.exposed_ms": 17.114305,
        "matmul_roofline": 86.72009525425254,
        "input.wait_ms": None, "step.compiled_hbm_gb": None}, rel=1e-12)
    b = run.breakdown()
    assert len(b["device_ops"]) == 10
    assert b["device_ops"][:2] == [["matmul fusion", 0.010277731],
                                   ["host dynamic-update-slice-done",
                                    0.009318059]]
    assert b["idle_gaps"][:2] == [["bench.loss_read", 2.228703766],
                                  ["bench.loss_read", 2.210253141]]
    # a trace of a program without scopes reads no scope
    assert not scoped.reduce_scopes(tr, scoped.metadata("")).scoped


def test_program_spans_leave_the_window_and_idle_gaps(tmp_path):
    """A real host trace (CPU): the program's spans, inside and outside
    the benchmark's, neither move the window nor name an idle gap."""
    f = jax.jit(lambda x: x + 1)
    f(1.0)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("data.get"):
        pass
    time.sleep(0.002)
    with jax.profiler.TraceAnnotation("bench.input"):
        with jax.profiler.TraceAnnotation("data.get"):
            time.sleep(0.001)
    with jax.profiler.TraceAnnotation("bench.step"):
        f(2.0)
    with jax.profiler.TraceAnnotation("bench.loss_read"):
        time.sleep(0.001)
    time.sleep(0.002)
    with jax.profiler.TraceAnnotation("data.get"):
        pass
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    tr = trace.Trace.from_xplane(str(path))
    assert [s[0] for s in tr.spans] == list(trace.SPANS)
    lo, hi = tr.window()
    assert (lo, hi) == (tr.spans[0][1], tr.spans[-1][2])
    spans = scoped.program_spans(str(path))
    assert [s[0] for s in spans] == ["data.get", "data.get", "data.get"]
    assert spans[0][1] < lo and spans[-1][1] > hi
    tr.ops["TPU:0"] = [trace.parse_op("%add.1 = f32[] add(%a)", lo + 10,
                                      5, False)]
    red = trace.reduce_trace(tr)
    assert {g for g, _ in red.idle_gaps} <= set(trace.SPANS) | {"none"}


# ---------------------------------------------------------------------------
# Readers
# ---------------------------------------------------------------------------

def _reads(cell, run):
    return {m: harness.load_metric(cell, m).read(run) for m in
            NEW + ("gather.stage1_ms",)}


def test_readers_of_the_scoped_run(recorded):
    tr, meta = recorded
    names = frozenset(meta["matmul_names"])
    base = trace.RunTrace(steps=2, red=trace.reduce_trace(tr, names),
                          input_s=[], matmul=None, compiled_bytes=None)
    ops = sorted({o.name for o in tr.ops["TPU:0"]})
    md = scoped.Metadata({n: f"jit(s)/jvp({scoped.SCOPES[i % 6]})/x"
                          for i, n in enumerate(ops)}, frozenset())
    lo = base.red.window[0]
    base._scoped = scoped.ScopedRun(
        base=base, red=scoped.reduce_scopes(tr, md),
        spans=[("data.get", lo, lo + 2_000_000),
               ("data.get", lo + 5, lo + 4_000_005)],
        counters={"setup_s": {"init_params": 4.5}, "init_peak_bytes": 3e9,
                  "step_lowerings": collections.deque([1, 0, 0, 0, 2],
                                                      maxlen=8)})
    got = _reads(harness.load_cell(QWEN), base)
    for m in ("attention.device_ms", "mlp.device_ms", "stack.slice_ms"):
        assert got[m] > 0
    assert got["remat.device_ms"] is None and got["gather.stage1_ms"] is None
    assert got["data.get_ms"] == pytest.approx(3.0)
    assert got["step.lowerings"] == 2              # the last two calls
    assert got["init.hbm_peak_gb"] == 3.0
    assert got["setup.init_params_s"] == 4.5
    assert 0 <= got["device.unscoped_share"] <= 100


def test_readers_of_a_program_without_scopes_read_nothing(recorded):
    tr, meta = recorded
    base = trace.RunTrace(steps=2, red=trace.reduce_trace(tr), input_s=[],
                          matmul=None, compiled_bytes=None)
    base._scoped = scoped.ScopedRun(
        base=base, red=scoped.reduce_scopes(tr, scoped.metadata("")),
        spans=[], counters=None)
    got = _reads(harness.load_cell(QWEN), base)
    assert all(v is None for v in got.values()), got


def test_scoped_run_is_found_in_the_frame_that_holds_the_run(monkeypatch):
    monkeypatch.setattr(scoped.ScopedRun, "read", classmethod(
        lambda cls, tdir, st, base: ("read", tdir, st)))
    ctx = trace.RunTrace(steps=1, red=trace.Reduction((0, 1)), input_s=[],
                         matmul=None, compiled_bytes=None)
    assert scoped.of(ctx) is None              # outside a run

    def run_frame(ctx, st, tdir):
        return scoped.of(ctx)
    assert run_frame(ctx, "st", "/tmp/x") == ("read", "/tmp/x", "st")
    assert scoped.of(ctx) == ("read", "/tmp/x", "st")      # kept


def test_traced_cpu_run_reads_the_program_counters(tmp_path, cpu_run):
    """A traced run of the smoke LoRA cell: the readers run inside
    ``harness.run``; the CPU has no device plane, so the device metrics
    stay out, and the counters and host spans read."""
    root = add_smoke_cells(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append("qwen-smoke-lora")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cpu_run.load_cell("qwen-smoke-lora", root)
    res = cpu_run.run(cell, 2**33 + 5, 0.3, True, jax.devices()[:1],
                      time.perf_counter(), log=lambda m: None)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["step.lowerings"] == 0
    assert got["data.get_ms"] > 0 and got["setup.init_params_s"] > 0
    assert "init.hbm_peak_gb" not in got      # no memory statistics
    assert "attention.device_ms" not in got    # no device plane


# ---------------------------------------------------------------------------
# The four-chip cell's files
# ---------------------------------------------------------------------------

def test_granite_cell_loads_on_four_chips():
    cell = harness.load_cell(GRANITE, REPO)
    mesh = cell.job["mesh"]
    assert cell.chips == 4 == mesh["pod"] * mesh["data"] * mesh["model"]
    assert mesh["pod"] == 2 and cell.peft is None
    assert cell.mix["batch"] == 4 and cell.mix["seq_len"] == 4096
    conf = next(c for c in json.loads((REPO / "BENCHMARK.json").read_text())
                ["configs"] if c["name"] == cell.config_name)
    assert set(conf["reduced"]) == set(cell.config["reduced"])
    assert set(conf["reduced"]) <= set(cell.config)
    m = cell.family.model_config(cell.config_name, cell.config)
    assert (m.d_model, m.num_heads, m.num_kv_heads, m.head_dim, m.d_ff,
            m.vocab_size, m.num_layers) == (4096, 32, 8, 128, 12800, 49155, 8)
    assert m.tie_embeddings and not m.qkv_bias
    names = [p["name"] for p in cell.per_layer]
    accepted = {"input.wait_ms", "device.idle_share", "host_cache.transfer_ms",
                "host_cache.exposed_ms", "matmul_roofline",
                "step.compiled_hbm_gb"}
    assert "gather.stage1_ms" in names and accepted <= set(names)
    assert set(NEW) <= set(names)
    assert set(cell.job["limits"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert "gather.stage1_ms" not in [
        p["name"] for p in harness.load_cell(QWEN, REPO).per_layer]

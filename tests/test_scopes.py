"""The program's named scopes, on the compiled step's HLO (CPU backend):
every layer's scope is there, nearly every fusion, dot, convolution and
collective falls under one, fcdp's stage-1 all-gathers run in the
forward only (zero3's also in the backward), and the scopes change
nothing in the compiled program but its metadata. And the counters a
run keeps of itself: lowerings per step, attention paths per traced
step, set-up phases, the memory peak at the end of set-up."""
import contextlib
import re

import jax
import pytest

from benchmarks.chip import scoped
from repro.configs.base import (OptimizerConfig, RunConfig, ShapeCell,
                                SystemConfig)
from repro.configs.registry import get_smoke_config
from repro.core.engine import StepBundle
from repro.launch.mesh import make_mesh
from repro.launch.train import LOWERINGS_KEPT
from repro.runtime import lowerings

CELL = ShapeCell("scopes", "train", 256, 4)
LAYER_SCOPES = {"stack", "attention", "mlp", "fcdp.gather2", "embed", "loss",
                "optimizer"}
COUNTED = ("fusion", "dot", "convolution")


def _run(arch, mode, peft, cell=CELL):
    lora = (dict(peft=True, lora_rank=4, lora_alpha=8.0,
                 lora_targets=("wq", "wk", "wv", "wo")) if peft else {})
    sysc = SystemConfig(mode=mode, activation_policy="block_io",
                        loss_chunk=64, min_shard_size=8, **lora)
    return RunConfig(model=get_smoke_config(arch), shape=cell, system=sysc,
                     optimizer=OptimizerConfig(total_steps=4, warmup_steps=1))


def _mesh(pods):
    if pods == 1:
        return make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    return make_mesh((2, 2, 1), ("pod", "data", "model"), jax.devices()[:4])


def compiled_text(arch, mode, peft, pods):
    b = StepBundle(_run(arch, mode, peft), _mesh(pods))
    return b.make_train_step().lower(*b.train_input_sds()).compile().as_text()


@pytest.fixture(scope="module")
def steps():
    """The smoke LoRA fcdp step on one device; the granite full
    fine-tune under fcdp and under zero3 on pod=2 x data=2."""
    return {"lora": compiled_text("qwen2.5-3b", "fcdp", True, 1),
            "fcdp": compiled_text("granite-3-8b", "fcdp", False, 2),
            "zero3": compiled_text("granite-3-8b", "zero3", False, 2)}


def instructions(text):
    """(name, opcode) of every instruction of the compiled text."""
    for line in text.splitlines():
        m = scoped._INSTR.match(line)
        if m:
            yield m.group(1), m.group(2)


@pytest.mark.parametrize("step,want", [
    ("lora", LAYER_SCOPES),
    ("fcdp", LAYER_SCOPES | {"fcdp.gather1"}),
])
def test_every_scope_appears_and_covers_the_step(steps, step, want):
    text = steps[step]
    md = scoped.metadata(text)
    seen = {scoped.scope_of(n) for n in md.op_name.values()} - {None}
    assert want <= seen
    counted = [n for n, op in instructions(text)
               if op in COUNTED or op.startswith(
                   ("all-gather", "reduce-scatter", "all-reduce",
                    "all-to-all", "collective-permute"))]
    under = [n for n in counted if scoped.scope_of(md.op_name.get(n, ""))]
    assert len(counted) > 100
    assert len(under) >= 0.95 * len(counted), (len(under), len(counted))


def _stage1_all_gathers(text):
    md = scoped.metadata(text)
    by_pass = {}
    for name, op in instructions(text):
        on = md.op_name.get(name, "")
        if op.startswith("all-gather") and scoped.scope_of(on) == \
                "fcdp.gather1":
            by_pass[scoped.pass_of(on)] = by_pass.get(scoped.pass_of(on),
                                                      0) + 1
    return by_pass


def test_fcdp_gathers_stage1_in_the_forward_only(steps):
    fcdp = _stage1_all_gathers(steps["fcdp"])
    zero3 = _stage1_all_gathers(steps["zero3"])
    # the paper's mechanism: fcdp caches the stage-1 shard in host memory
    # and never gathers it again; zero3 gathers it again in the backward
    assert fcdp.get("forward", 0) > 0 and fcdp.get("backward", 0) == 0
    assert zero3.get("forward", 0) > 0 and zero3.get("backward", 0) > 0


def _strip(text):
    """The module without its metadata: the debug tables (file names,
    stack frames) between the header and the first computation, and each
    instruction's ``metadata={...}``."""
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    body = "\n".join([lines[0]] + lines[first:])
    return re.sub(r",? metadata=\{[^}]*\}", "", body)


def test_scopes_change_only_metadata(steps, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc
    with_scopes = steps["lora"]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    # the persistent cache's key leaves out the metadata: a cache left on
    # by an earlier test would hand back the scoped step
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        without = compiled_text("qwen2.5-3b", "fcdp", True, 1)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    assert "attention/" not in without and "attention/" in with_scopes
    assert _strip(with_scopes) == _strip(without)


# ---------------------------------------------------------------------------
# Counters
# ---------------------------------------------------------------------------

def test_lowerings_count_new_programs_only():
    f = jax.jit(lambda x: x * 3 + 1)
    n0 = lowerings.count()
    f(1.0)
    n1 = lowerings.count()
    f(2.0)
    assert n1 - n0 >= 1 and lowerings.count() == n1


@pytest.fixture(scope="module")
def run_state():
    from repro.launch.train import RunState
    cell = ShapeCell("scopes", "train", 64, 1)
    return RunState(_run("qwen2.5-3b", "fcdp", True, cell), _mesh(1), None)


def test_run_counts_lowerings_per_step(run_state):
    from repro.data.pipeline import DataConfig, ShardedLoader, \
        SyntheticPackedLM
    st = run_state
    st.do_train_step(st.loader.get(0))
    st.do_train_step(st.loader.get(1))
    first, warm = list(st.counters["step_lowerings"])[-2:]
    assert first >= 1 and warm == 0
    # a batch of another shape recompiles, and the counter names the step
    longer = ShapeCell("scopes", "train", 128, 1)
    loader = ShardedLoader(SyntheticPackedLM(st.run.model, longer,
                                             DataConfig(0)), st.mesh,
                           st.bundle.batch_spec(longer))
    st.do_train_step(loader.get(2))
    assert st.counters["step_lowerings"][-1] >= 1
    assert len(st.counters["step_lowerings"]) == st.steps_taken
    # kept for the latest calls only, however long the run
    assert st.counters["step_lowerings"].maxlen == LOWERINGS_KEPT


def test_run_counts_attention_paths(run_state):
    """The step's trace records its self-attention calls by path: all
    chunked on the CPU; a warm step leaves the count as it was."""
    st = run_state
    st.do_train_step(st.loader.get(0))
    paths = st.counters["attention_paths"]
    assert paths["kernel"] == 0 and paths["chunked"] >= 1
    st.do_train_step(st.loader.get(1))
    assert st.counters["attention_paths"] == paths


def test_run_counts_its_set_up(run_state):
    c = run_state.counters
    assert set(c["setup_s"]) == {"bundle", "init_params", "init_opt"}
    assert all(v > 0 for v in c["setup_s"].values())
    # the CPU backend keeps no memory statistics
    assert jax.devices()[0].memory_stats() is None
    assert c["init_peak_bytes"] is None

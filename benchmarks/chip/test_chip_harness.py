"""The harness is driven by data: a model family, a configuration, a
traffic mix, a cell and a per-layer metric added as new files (and
entries of BENCHMARK.json) are found by name and run, with no file
edited. And the command refuses to run without a TPU."""
import hashlib
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from benchmarks.chip import harness, trace
from benchmarks.chip.conftest import REPO, SMOKE_WIDTHS, add_smoke_cells

HERE = Path(__file__).resolve().parent


def _hashes(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_cell_and_metric_are_found_by_name(tmp_path, cpu_run):
    root = add_smoke_cells(tmp_path)
    before = _hashes(root)
    b = root / "benchmarks" / "chip"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    # a family of its own (here made of the dense family's functions), a
    # configuration of it, its own traffic and cell
    (b / "families/twin.py").write_text(
        "from benchmarks.chip.families.dense import (  # noqa: F401\n"
        "    model_config, model_flops_per_token, param_specs,\n"
        "    reference_model)\n")
    cfg = json.loads((b / "configs/qwen2.5-3b-lora.json").read_text())
    cfg.update(SMOKE_WIDTHS, vocab_size=300, attention_bias=False,
               tie_word_embeddings=False, num_hidden_layers=3, family="twin")
    (b / "configs/extra-smoke.json").write_text(json.dumps(cfg))
    job = json.loads((b / "cells/qwen-smoke-lora.json").read_text())
    job["mode"] = "zero3"
    job["system"] = {"prefetch_depth": 0}
    (b / "cells/extra-smoke-zero3.json").write_text(json.dumps(job))
    (b / "traffic/extra-1x128.json").write_text(json.dumps(dict(
        kind="packed_lm", batch=1, seq_len=128, zipf_a=1.1, doc_len_mean=32,
        eod_token=0)))
    (b / "metrics/extra.steps.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    bench["configs"].append(dict(name="extra-smoke", source="smoke", why="x",
                                 file="benchmarks/chip/configs/extra-smoke.json",
                                 reduced=[]))
    bench["workloads"].append(dict(name="extra-smoke-zero3", why="x",
                                   config="extra-smoke", traffic="extra-1x128",
                                   chips=1))
    bench["per_layer"].append(dict(name="extra.steps", unit="steps",
                                   better="higher", source="host_clock",
                                   layer="input pipeline",
                                   moves="tokens_per_s",
                                   workloads=["extra-smoke-zero3"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    harness = cpu_run
    cell = harness.load_cell("extra-smoke-zero3", root)
    assert cell.family.__file__ == str(b / "families/twin.py")
    assert cell.config["num_hidden_layers"] == 3
    assert cell.mix["seq_len"] == 128 and cell.job["mode"] == "zero3"
    assert harness.run_config(cell, 1).system.prefetch_depth == 0
    assert [m["name"] for m in cell.per_layer] == ["extra.steps"]
    res = harness.run(cell, 12345, 0.5, False, jax.devices()[:1],
                      time.perf_counter(), log=lambda m: None)
    assert res["correct"], res["checks"]
    assert res["metrics"]["tokens_per_s"]["value"] > 0

    reader = harness.load_metric(cell, "extra.steps")
    red = trace.reduce_trace(trace.Trace.from_json(
        str(HERE / "testdata" / "trace_qwen_1chip.json.gz")))
    run = trace.RunTrace(steps=3, red=red, input_s=[], matmul=None,
                         compiled_bytes=None)
    assert reader.read(run) == 3.0
    # only new files and BENCHMARK.json's new entries: no file of the
    # benchmark that was there before was edited
    after = _hashes(root)
    del before[Path("BENCHMARK.json")]
    assert {k: after[k] for k in before} == before


@pytest.mark.parametrize("where", ["repo", "smoke"])
def test_each_pair_of_config_and_traffic_is_one_cell(where, tmp_path):
    root = REPO if where == "repo" else add_smoke_cells(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {c["name"] for c in bench["configs"]} == {p[0] for p in pairs}


def test_zero3_cell_runs_the_fcdp_cells_model():
    fcdp = harness.load_cell("qwen2.5-3b-lora-fcdp-4k", REPO)
    zero3 = harness.load_cell("qwen2.5-3b-lora-zero3-4k", REPO)
    assert fcdp.config_name != zero3.config_name
    model = [{k: v for k, v in c.config.items() if k != "deployment"}
             for c in (fcdp, zero3)]
    assert model[0] == model[1]
    assert fcdp.mix == zero3.mix
    job = dict(fcdp.job, mode="zero3")
    del job["limits"]
    assert job == {k: v for k, v in zero3.job.items() if k != "limits"}


def test_command_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", "--workload",
         "qwen2.5-3b-lora-fcdp-4k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

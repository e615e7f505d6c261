"""Fixtures of the benchmark's own tests. They run on the CPU backend
with virtual devices, never on a chip, at smoke sizes: a copy of the
benchmark under a temporary root, with three smoke cells added as files
(a LoRA cell on one device under fcdp and under zero3, and a full
fine-tune on pod=2 x data=2)."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import json  # noqa: E402
import shutil  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"

SMOKE_WIDTHS = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2, head_dim=16)
# Set from the smoke cells' readings on the CPU over seeds 11, 12, 21-24:
# sound runs read at most 3.5e-5 (loss) / 5.7e-3 (grad) / 4.1e-3
# (change); the fp8 control at least 1.6e-4 / 1.6e-2 / 3.9e-3, so it
# fails the loss limit on every seed read.
SMOKE_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1.2e-2, "change_gap": 1.2e-2}


def add_smoke_cells(root: Path) -> Path:
    """Copy the benchmark under ``root`` and add the smoke cells, each as
    files of its own plus an entry of BENCHMARK.json."""
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "test*"))
    b = root / "benchmarks" / "chip"
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    # the smoke cells start from the qwen cell's files: a LoRA job on one
    # device under fcdp and under zero3 (each with a configuration of its
    # own, since a pair of configuration and traffic is one cell), and a
    # full fine-tune of a Granite-like configuration (no bias, untied
    # vocabulary of odd size) on pod=2 x data=2
    cells = (("qwen-smoke", "qwen-smoke-lora", 1, dict(vocab_size=512), {}),
             ("qwen-smoke-zero3", "qwen-smoke-lora-zero3", 1,
              dict(vocab_size=512), dict(mode="zero3")),
             ("granite-smoke", "granite-smoke-full", 4,
              dict(vocab_size=515, attention_bias=False, rope_theta=1e4,
                   rms_norm_eps=1e-5),
              dict(peft=None, mesh={"pod": 2, "data": 2, "model": 1})))
    for conf, cell, chips, cfg_extra, job_extra in cells:
        cfg = json.loads((b / "configs/qwen2.5-3b-lora.json").read_text())
        cfg.update(SMOKE_WIDTHS, **cfg_extra)
        (b / f"configs/{conf}.json").write_text(json.dumps(cfg))
        job = json.loads((b / "cells/qwen2.5-3b-lora-fcdp-4k.json").read_text())
        job.update(min_shard_size=8, limits=SMOKE_LIMITS, **job_extra)
        (b / f"cells/{cell}.json").write_text(json.dumps(job))
        traffic = f"smoke-{chips}x256"
        (b / f"traffic/{traffic}.json").write_text(json.dumps(dict(
            kind="packed_lm", batch=chips, seq_len=256, zipf_a=1.2,
            doc_len_mean=64, eod_token=0)))
        if conf not in {c["name"] for c in bench["configs"]}:
            bench["configs"].append(dict(
                name=conf, source="smoke", reduced=[], why="smoke size",
                file=f"benchmarks/chip/configs/{conf}.json"))
        bench["workloads"].append(dict(name=cell, config=conf, traffic=traffic,
                                       chips=chips, why="smoke size"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.fixture(scope="session")
def smoke_root(tmp_path_factory):
    return add_smoke_cells(tmp_path_factory.mktemp("bench"))


@pytest.fixture()
def cpu_run(monkeypatch):
    """Drive ``harness.run`` on the CPU: the compile cache stays off and
    the CPU gets a row in the table of peaks (its numbers are not
    device metrics and are never reported)."""
    from benchmarks.chip import flops, harness
    monkeypatch.setattr(harness, "init_cache", lambda: None)
    real = flops.peaks
    monkeypatch.setattr(flops, "peaks", lambda kind: (
        {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11} if kind == "cpu"
        else real(kind)))
    return harness

"""Whole runs of the harness at smoke size on the CPU (the look for a
chip skipped), sound and with the timed path broken underneath: each
fault a cell can have must turn ``correct`` false."""
import contextlib
import time

import jax
import pytest

from benchmarks.chip import faults

SEED = 3_000_000_019


def run_cell(harness, root, cell, fault=None, monkeypatch=None):
    c = harness.load_cell(cell, root)
    stack = contextlib.ExitStack()
    if fault == "no_exchange":
        stack.enter_context(faults.no_exchange())
    elif fault:
        build = harness.build

        def planted(*a, **k):
            st = build(*a, **k)
            stack.enter_context(faults.FAULTS[fault](st))
            return st
        monkeypatch.setattr(harness, "build", planted)
    with stack:
        return harness.run(c, SEED, 0.5, False, jax.devices()[:c.chips],
                           time.perf_counter(), log=lambda m: None)


@pytest.mark.parametrize("cell,fault", [
    ("qwen-smoke-lora", None),
    ("qwen-smoke-lora", "unchanged_state"),
    ("qwen-smoke-lora", "half_batch"),
    ("qwen-smoke-lora-zero3", None),
    ("qwen-smoke-lora-zero3", "unchanged_state"),
    ("qwen-smoke-lora-zero3", "half_batch"),
    ("granite-smoke-full", None),
    ("granite-smoke-full", "half_batch"),
    ("granite-smoke-full", "no_exchange"),
])
def test_fault_turns_correct_false(smoke_root, cpu_run, monkeypatch, cell,
                                   fault):
    res = run_cell(cpu_run, smoke_root, cell, fault, monkeypatch)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "mfu", "hbm_peak_gb",
                                   "setup_s"}
    assert res["device"]["count"] == (4 if "granite" in cell else 1)

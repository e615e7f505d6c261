"""The benchmark's engine: find a cell by name, build the program's own
training run for it, warm it up, time a window, and decide ``correct``.

Everything that belongs to one model family, configuration, traffic
mix, cell or per-layer metric is a file of its own, found by the name
that ``BENCHMARK.json`` (or the configuration) gives it:

- ``families/<family>.py``: what is particular to a model family: the
  program's ``ModelConfig`` (``model_config``), the reference's leaves
  and layer groups (``param_specs``, ``reference_model``) and the FLOPs
  rule of ``mfu`` (``model_flops_per_token``);
- ``configs/<config>.json``: the model's published keys, as run, beside
  the benchmark's own: ``family`` (the module above; there is no
  default), ``reduced``, ``cuts``, ``assumed``, ``deployment`` and
  ``departures``;
- ``traffic/<traffic>.json``: the batch, row length and generator
  parameters, read by ``traffic.PackedLM``;
- ``cells/<workload>.json``: the job (strategy, mesh, remat, loss chunk,
  LoRA, optimizer, and under ``system`` any further ``SystemConfig``
  fields, passed as they are) and the limits of the comparison that
  decides ``correct``;
- ``metrics/<name>.py``: a reader with ``read(run) -> float | None``.

So a configuration of a new family comes as new files only: its family
module, its configuration, a cell and its traffic, and readers for any
metric of its own.

The window drives the program's own path: ``ShardedLoader.get`` and
``RunState.do_train_step``, then a read of the step's loss, as
``repro.launch.train``'s loop does. The benchmark has no train step of
its own.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import re
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import jax

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = Path(__file__).resolve().parent.relative_to(ROOT)
WARMUP_STEPS = 3          # the compile step and two more; the reference
#                           follows these same three steps


class NoChip(Exception):
    """No TPU, or not as many chips as the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # configs/<config>.json
    job: dict               # cells/<workload>.json
    mix: dict               # traffic/<traffic>.json
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    family: ModuleType      # families/<family>.py of the configuration
    root: Path = ROOT

    @property
    def bench_dir(self) -> Path:
        return self.root / BENCH_DIR

    @property
    def peft(self) -> Optional[dict]:
        return self.job.get("peft") or None


def _read_json(path: Path) -> dict:
    return json.loads(path.read_text())


def _import(path: Path, prefix: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(f"{prefix}{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(config_file: Path, cfg: dict, bench_dir: Path) -> ModuleType:
    """The module ``families/<family>.py`` that the configuration's
    ``family`` key names. A missing key or module raises; there is no
    default family."""
    name = cfg.get("family")
    if not (isinstance(name, str)
            and re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", name)):
        raise ValueError(f"{config_file}: no \"family\" key naming a module "
                         f"{bench_dir}/families/<family>.py (got {name!r})")
    path = bench_dir / "families" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{config_file}: family {name!r} has no "
                                f"module {path}")
    return _import(path, "bench_family_")


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` as ``BENCHMARK.json`` under ``root`` names
    it, with its configuration, traffic mix and job files."""
    bench = _read_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; have {sorted(by_name)}")
    w = by_name[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    bdir = root / BENCH_DIR
    config_file = root / conf["file"]
    config = _read_json(config_file)

    def mine(entry):
        return "workloads" not in entry or workload in entry["workloads"]

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=conf["name"], config=config,
                job=_read_json(bdir / "cells" / f"{workload}.json"),
                mix=_read_json(bdir / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer,
                family=load_family(config_file, config, bdir), root=root)


def require_tpu(chips: int):
    """The TPU devices of this machine: exactly ``chips`` of them, or
    NoChip. There is no CPU fallback."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform is {devs[0].platform!r}); "
                     "the benchmark measures the chip only")
    if len(devs) != chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s), found {len(devs)}")
    return devs


def run_config(cell: Cell, seed: int):
    from repro.configs.base import (OptimizerConfig, RunConfig, ShapeCell,
                                    SystemConfig)
    job, peft = cell.job, cell.peft
    lora = ({} if not peft else
            {"peft": True, "lora_rank": peft["rank"],
             "lora_alpha": peft["alpha"],
             "lora_targets": tuple(peft["targets"])})
    sysc = SystemConfig(mode=job["mode"],
                        activation_policy=job["activation_policy"],
                        loss_chunk=job["loss_chunk"],
                        min_shard_size=job["min_shard_size"], **lora,
                        **job.get("system", {}))
    shape = ShapeCell(cell.name, "train", int(cell.mix["seq_len"]),
                      int(cell.mix["batch"]))
    return RunConfig(model=cell.family.model_config(cell.config_name,
                                                    cell.config),
                     shape=shape, system=sysc,
                     optimizer=OptimizerConfig(**job["optimizer"]), seed=seed)


def make_mesh(mesh: dict, devices):
    """The launcher's mesh (a pod axis of 1 left out) over ``devices``."""
    from repro.launch.mesh import make_mesh as mk
    pod, data, model = mesh["pod"], mesh["data"], mesh["model"]
    if pod * data * model != len(devices):
        raise ValueError(f"mesh {mesh} needs {pod * data * model} devices, "
                         f"{len(devices)} given")
    if pod > 1:
        return mk((pod, data, model), ("pod", "data", "model"), devices)
    return mk((data, model), ("data", "model"), devices)


def build(cell: Cell, seed: int, devices):
    """The program's RunState for this cell, fed by the benchmark's
    traffic. Weights come from ``seed`` through the program's own
    initialisation, as ``repro.launch.train.build`` makes them."""
    from repro.data.pipeline import ShardedLoader
    from repro.launch.train import RunState

    from benchmarks.chip.traffic import PackedLM
    run = run_config(cell, seed)
    st = RunState(run, make_mesh(cell.job["mesh"], devices), None)
    st.loader = ShardedLoader(
        PackedLM(cell.mix, run.model.vocab_size, seed), st.mesh,
        st.bundle.batch_spec(run.shape))
    return st


# ---------------------------------------------------------------------------
# Readings of the program's state for the comparison
# ---------------------------------------------------------------------------

@jax.jit
def _sq_norms(leaves):
    return jax.numpy.stack([jax.numpy.sum(jax.numpy.square(
        x.astype(jax.numpy.float32))) for x in leaves])


def train_labels(st) -> List[str]:
    b = st.bundle
    return [b.def_leaves[i].label for i in b.train_idx]


def first_grad_norms(st) -> Dict[str, float]:
    """Per-leaf norm of the first gradient as the optimizer received it
    (after clipping), read back from AdamW's first moment after one
    step: m_1 = (1 - b1) g_1."""
    import numpy as np
    b1 = st.run.optimizer.b1
    sq = np.asarray(_sq_norms(st.opt["m"]), np.float64)
    return {k: float(math.sqrt(v) / (1 - b1))
            for k, v in zip(train_labels(st), sq)}


def change_norms(st, cell: Cell, seed: int) -> Dict[str, float]:
    """Per-leaf norm of (f32 master after the warm-up steps - its
    initial value). The initial value is drawn again from the seed by the
    reference's own initialisation, on the device, a leaf at a time."""
    from benchmarks.chip import reference
    specs = cell.family.param_specs(cell.config, cell.peft)
    keys = reference.leaf_keys(seed, len(specs))
    index = {s.path: i for i, s in enumerate(specs)}
    out = {}
    for label, master in zip(train_labels(st), st.opt["master"]):
        i = index[label]
        out[label] = float(reference.moved_norm(master, keys[i], specs[i]))
    return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclass
class Window:
    steps: int = 0
    seconds: float = 0.0
    losses: List[float] = field(default_factory=list)
    input_s: List[float] = field(default_factory=list)


def drive(st, step: int, win: Optional[Window] = None) -> float:
    """One iteration of the launcher's loop: place the step's batch, run
    the train step, read its loss (which waits for the step). Each part
    is a host span on the profiler's clock."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.input"):
        batch = st.loader.get(step)
    t1 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.step"):
        m = st.do_train_step(batch)
    with jax.profiler.TraceAnnotation("bench.loss_read"):
        loss = float(m["loss"])
    if win is not None:
        win.input_s.append(t1 - t0)
    return loss


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def free_state(st) -> None:
    for x in jax.tree.leaves((st.train_p, st.frozen_p, st.opt)):
        x.delete()
    st.train_p = st.frozen_p = st.opt = None
    gc.collect()


def load_metric(cell: Cell, name: str):
    return _import(cell.bench_dir / "metrics" / f"{name}.py", "bench_metric_")


def init_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed path,
    for every program of a run, however short its compile."""
    from repro.launch.cli import init_compile_cache
    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, log=print) -> dict:
    """One run of ``cell``: set-up and warm-up, a window of ``seconds``,
    then the comparison with the reference. Returns the result line."""
    from benchmarks.chip import correct, flops, reference
    from benchmarks.chip import trace as trace_mod
    init_cache()
    st = build(cell, seed, devices)
    warm = []
    for step in range(WARMUP_STEPS):
        warm.append(drive(st, step))
        if step == 0:
            g1 = first_grad_norms(st)
    delta = change_norms(st, cell, seed)
    prog = correct.Readings(losses=warm, grad_norms=g1, change_norms=delta)

    win = Window()
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir)
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    step = WARMUP_STEPS
    while True:
        win.losses.append(drive(st, step, win))
        step += 1
        win.steps += 1
        if time.perf_counter() - t0 >= seconds:
            break
    win.seconds = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    peak = memory_peak(devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    B, S = int(cell.mix["batch"]), int(cell.mix["seq_len"])
    failed = sum(not math.isfinite(x) for x in win.losses)

    out = {"metrics": {}}
    if trace:
        ctx = trace_mod.RunTrace.read(tdir, st, cell, win, devices)
        device["busy_s"], device["window_s"] = ctx.busy_s, ctx.window_s
        for m in cell.per_layer:
            v = load_metric(cell, m["name"]).read(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = ctx.breakdown()
        trace_mod.remove(tdir)
    else:
        tps = win.steps * B * S / win.seconds
        fpt = cell.family.model_flops_per_token(cell.config, S, cell.peft)
        peak_flops = flops.peaks(dev.device_kind)["bf16_flops"]
        values = {"tokens_per_s": tps,
                  "mfu": 100.0 * tps * fpt / (len(devices) * peak_flops),
                  "hbm_peak_gb": peak / 1e9, "setup_s": setup_s}
        for m in cell.end_to_end:
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    log(f"window: {win.steps} steps in {win.seconds:.3f} s, losses "
        f"{[round(x, 4) for x in win.losses]}; setup {setup_s:.2f} s; "
        f"peak {peak} B")

    free_state(st)
    ref = reference.train(cell.family, cell.config, cell.job, cell.mix,
                          seed, WARMUP_STEPS, devices)
    checks = correct.compare(prog, ref, cell.job["limits"])
    ok = failed == 0 and all(c["value"] <= c["limit"]
                             for c in checks.values())
    result = {"correct": ok, "attempted": win.steps, "failed": failed}
    result.update(out)
    result["device"] = device
    result["checks"] = checks
    return result

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, print memory/cost analysis, and persist
roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b --cell train_4k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell, both meshes
  PYTHONPATH=src python -m repro.launch.dryrun --all --mode zero3
"""
import argparse
import gc
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import (RunConfig, SystemConfig, shape_cell,
                                SHAPE_CELLS)
from repro.configs.registry import (ARCH_IDS, cell_supported, get_config)
from repro.core.engine import StepBundle
from repro.core.strategy import DEFAULT_STRATEGY
from repro.launch.cli import (add_system_args, init_compile_cache,
                              system_config_from_args)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (collect_collectives, flops_bytes_from_jaxpr,
                                   fused_overlap_credit,
                                   parse_stablehlo_counts, roofline_report)

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results"


def _mesh_sizes(mesh):
    return {a: int(mesh.shape[a]) for a in mesh.axis_names}


def dryrun_cell(arch: str, cell_name: str, multi_pod: bool,
                mode: str = DEFAULT_STRATEGY, system_overrides=None,
                verbose: bool = True, prefetch_depth=None,
                mode_overrides=(), microbatch: int = 0,
                async_grad_reduce: bool = False,
                cross_step: bool = False, param_compress: str = "none",
                fused_matmul: str = "none", system: SystemConfig = None):
    """mode_overrides: per-tensor strategy rules ((path-glob, mode), ...)
    layered on top of ``mode`` -- the dry-run reports the per-group
    byte breakdown whenever the resolution is mixed.

    cross_step lowers the STEADY-STATE (piped) step of the cross-step
    optimizer pipeline (requires async_grad_reduce and microbatch >= 2);
    its per-step DCN volume is byte-identical to the fused step, and the
    JSON additionally carries ``cross_step_buffer_bytes_per_chip``.

    system: a pre-built SystemConfig (the shared launch/cli.py surface)
    used as-is, superseding the individual knob kwargs above; the
    dry-run still pins its loss_chunk=2048 + block_io policy (the
    HBM-fitting defaults every table is defined on) unless
    system_overrides says otherwise."""
    cfg = get_config(arch)
    cell = shape_cell(cell_name)
    if system is not None:
        mode = system.mode
    ok, why = cell_supported(cfg, cell)
    if not ok:
        return {"arch": arch, "cell": cell_name, "multi_pod": multi_pod,
                "mode": mode, "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    # block_io (full activation remat) is the HBM-fitting default on
    # 16 GB v5e at the assigned shapes; the paper-faithful save_all
    # variant is compared in benchmarks/bench_memory.py (see EXPERIMENTS.md)
    if system is None:
        if prefetch_depth is None:
            prefetch_depth = 1      # dry-run's historical overlap-on default
        system = SystemConfig(mode=mode, prefetch_depth=prefetch_depth,
                              async_grad_reduce=async_grad_reduce,
                              cross_step_pipeline=cross_step,
                              param_compress=param_compress,
                              fused_matmul=fused_matmul,
                              mode_overrides=tuple(mode_overrides or ()))
    sysc = system.replace(loss_chunk=2048, activation_policy="block_io")
    if system_overrides:
        sysc = sysc.replace(**system_overrides)
    fused_matmul = sysc.fused_matmul
    run = RunConfig(model=cfg, shape=cell, system=sysc,
                    microbatch=microbatch)
    t0 = time.time()
    bundle = StepBundle(run, mesh)
    # the depth the streaming gather scheduler actually runs at on this
    # (mode x mesh x cell) -- mirrored into the roofline overlap model.
    # The scheduler drives serve scans too; cells whose plans have no
    # stage 1 (serve_frozen fcdp layouts) report ~zero pod-AG bytes and
    # get no credit regardless.
    from repro.core.cache import cache_bytes_per_chip
    kv = None
    if cell.kind == "decode":
        from repro.core.engine.serve import check_paged_plan, default_paged_kv
        try:
            check_paged_plan(bundle.model)
            kv = default_paged_kv(bundle, cell)
        except ValueError:
            kv = None       # paged serving not supported for this plan
    acct = cache_bytes_per_chip(bundle, kv=kv)
    depth_live = acct["prefetch_depth"]
    seq_sharded = (cell.name == "long_500k")
    if cell.kind == "train":
        step = bundle.make_train_step()
        sds = bundle.train_input_sds()
    elif cell.kind == "prefill":
        step = bundle.make_prefill_step()
        sds = bundle.prefill_input_sds()
    else:
        step = bundle.make_decode_step(seq_sharded=seq_sharded)
        sds = bundle.decode_input_sds(seq_sharded=seq_sharded)

    lowered = step.lower(*sds)
    t_lower = time.time() - t0
    slo_counts = parse_stablehlo_counts(lowered.as_text())
    # jaxpr walk for exact collective accounting (axis attribution + scan
    # trip counts; compiled HLO on CPU CSEs remat'd gathers, so the jaxpr
    # is the faithful source -- see DESIGN.md)
    closed = step.trace(*sds).jaxpr
    n_chips = mesh.devices.size
    stats = collect_collectives(closed, _mesh_sizes(mesh))
    flops_exact, bytes_naive = flops_bytes_from_jaxpr(closed, n_chips)

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops_ca = float(ca.get("flops", 0.0))     # lower bound: loops counted 1x
    bytes_ca = float(ca.get("bytes accessed", 0.0))
    fused_credit = fused_overlap_credit(
        bundle.def_leaves, bundle.plan_leaves, _mesh_sizes(mesh), cell,
        tp=bundle.mi.tp)
    rep = roofline_report(
        flops_exact, bytes_naive, stats, cfg, cell, n_chips,
        prefetch=depth_live,
        inflight_bytes=acct["prefetch_buffer_bytes_per_chip"],
        group_bytes=acct["by_group"],
        cross_step=acct["cross_step"],
        cross_step_bytes=acct["cross_step_buffer_bytes_per_chip"],
        fused=fused_credit)
    result = {
        "arch": arch, "cell": cell_name, "multi_pod": multi_pod,
        "mode": mode, "status": "ok",
        "mode_overrides": list(map(list, sysc.mode_overrides)),
        "n_chips": n_chips,
        "prefetch_depth": depth_live,
        "prefetch_buffer_bytes_per_chip":
            acct["prefetch_buffer_bytes_per_chip"],
        "async_buffer_bytes_per_chip":
            acct["async_buffer_bytes_per_chip"],
        "cross_step": acct["cross_step"],
        "cross_step_buffer_bytes_per_chip":
            acct["cross_step_buffer_bytes_per_chip"],
        "param_compress": acct["param_compress"],
        "kv_page_bytes_per_chip": acct["kv_page_bytes_per_chip"],
        "fused_matmul": fused_matmul,
        "fused_n_leaves": fused_credit["n_fused_leaves"],
        "fused_overlap_credit_s": fused_credit["credit_s"],
        "stage1_dcn_gather_bytes_per_chip":
            acct["stage1_dcn_gather_bytes_per_chip"],
        "stage1_dcn_gather_bytes_exact":
            acct["stage1_dcn_gather_bytes_exact"],
        "cache_by_group": acct["by_group"],
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_est_bytes": ma.argument_size_in_bytes
            + ma.temp_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "flops_per_chip": flops_exact,
        "bytes_per_chip": bytes_naive,
        "flops_cost_analysis": flops_ca,
        "bytes_cost_analysis": bytes_ca,
        "stablehlo_collectives": slo_counts,
        "roofline": rep,
    }
    if verbose:
        mem = result["memory"]
        print(f"[{arch} x {cell_name} x {'2pod' if multi_pod else '1pod'} "
              f"x {mode}] compile={t_compile:.1f}s "
              f"args={mem['argument_bytes']/2**30:.2f}GiB "
              f"temp={mem['temp_bytes']/2**30:.2f}GiB "
              f"flops/chip={flops_exact:.3e} "
              f"dom={rep['dominant']} roofline={rep['roofline_fraction']:.3f}")
        print(f"  memory_analysis: {ma}")
        print(f"  cost_analysis (1x-loop lower bounds): "
              f"flops={flops_ca:.4g} bytes={bytes_ca:.4g}")
    del compiled, lowered, step, bundle
    gc.collect()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS) + [None])
    ap.add_argument("--cell", default=None,
                    choices=[c.name for c in SHAPE_CELLS] + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    # the dry-run keeps its historical overlap-on default (depth 1);
    # --prefetch-depth 0 is the old --no-prefetch
    add_system_args(ap, default_prefetch_depth=1)
    ap.add_argument("--microbatch", type=int, default=0,
                    help="gradient-accumulation microbatches for train "
                         "cells (required >= 2 for --cross-step-pipeline)")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x cell) on both meshes")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.cross_step_pipeline and (not args.async_grad_reduce
                                     or args.microbatch < 2):
        # catch flag misuse at the CLI, not as a per-cell "system bug"
        # traceback inside the sweep loop
        ap.error("--cross-step-pipeline requires --async-grad-reduce "
                 "and --microbatch >= 2")

    init_compile_cache()
    RESULTS_DIR.mkdir(exist_ok=True)
    results = []
    if args.all:
        combos = [(a, c.name, mp) for a in ARCH_IDS for c in SHAPE_CELLS
                  for mp in (False, True)]
    else:
        archs = [args.arch] if args.arch else list(ARCH_IDS)
        cells = [args.cell] if args.cell else [c.name for c in SHAPE_CELLS]
        pods = []
        if args.multi_pod or not args.single_pod:
            pods.append(True)
        if args.single_pod or not args.multi_pod:
            pods.append(False)
        combos = [(a, c, mp) for a in archs for c in cells for mp in pods]

    sysc = system_config_from_args(args)
    failures = 0
    for arch, cell, mp in combos:
        try:
            r = dryrun_cell(arch, cell, mp, system=sysc,
                            microbatch=args.microbatch)
        except Exception as e:  # a failure here is a bug in the system
            traceback.print_exc()
            r = {"arch": arch, "cell": cell, "multi_pod": mp,
                 "mode": args.mode, "status": "FAILED",
                 "error": f"{type(e).__name__}: {e}"}
            failures += 1
        results.append(r)
        if r["status"] == "skipped":
            print(f"[{arch} x {cell} x {'2pod' if mp else '1pod'}] "
                  f"SKIP: {r['reason']}")

    out = args.out or (RESULTS_DIR / (
        f"dryrun_{args.mode}"
        f"{'_mixed' if sysc.mode_overrides else ''}.json"))
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"\nwrote {out}; {len(results)} cells, {failures} failures")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

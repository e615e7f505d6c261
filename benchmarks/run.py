import os
import sys
# The comm/memory/throughput benches analyse the production meshes, which
# requires the 512-device host platform BEFORE jax initializes. This is
# deliberate and local to this entrypoint (smoke tests see 1 device).
# --smoke uses an 8-device toy mesh instead so CI finishes in minutes.
_N_DEV = 8 if "--smoke" in sys.argv else 512
os.environ.setdefault("XLA_FLAGS",
                      f"--xla_force_host_platform_device_count={_N_DEV}")

"""Benchmark driver -- one workload per paper table/figure.

  paper artifact            -> workload
  Table VII (comm volume)   -> comm_volume
  Tables V/VI (max batch)   -> max_batch
  Fig. 5/6 (throughput)     -> throughput_model
  Fig. 9 (bw sensitivity)   -> bw_sensitivity
  SS III-B (memory)         -> memory
  kernels (substrate)       -> kernels

The axis bodies, timed arms, and artifact schemas live in
``benchmarks/harness/`` (workloads / execution / results); this file
only selects the workload list, drives each axis, and reports.

``--smoke`` runs the reduced toy-mesh matrix (one axis per subsystem,
every analytic acceptance assertion); add ``--timed`` to ALSO measure
warmed-up wall-clock step times (median/p90 over N fenced steps) for
the declared arms of each axis.  Every invocation writes a timestamped
run dir ``results/runs/<stamp>/`` (manifest.json + one schema-validated
artifact per axis) -- the unit ``benchmarks/compare.py`` diffs against
``results/baseline/`` -- plus the flat ``results/bench_smoke_*.json``
files older consumers glob.

Prints ``name,us_per_call,derived`` CSV per the repo convention.
"""
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

import argparse
import json
import time
import traceback

from benchmarks.harness import execution, results, workloads

RESULTS = results.RESULTS


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI path: kernel oracles + toy-mesh comm "
                         "schema check + mixed-mode dry-run + cross-step "
                         "on/off axis + crash-resume parity")
    ap.add_argument("--timed", action="store_true",
                    help="also measure wall-clock step times for each "
                         "axis's declared arms (warmup excluded, "
                         "block_until_ready fenced, median/p90)")
    ap.add_argument("--warmup-steps", type=int, default=2,
                    help="steps run before the timed region per arm")
    ap.add_argument("--timed-steps", type=int, default=5,
                    help="individually timed steps per arm")
    ap.add_argument("--axis", action="append", default=[],
                    help="run only the named axes (repeatable)")
    ap.add_argument("--mode-override", action="append", default=[],
                    metavar="GLOB=MODE",
                    help="per-tensor strategy override applied on top of "
                         "every bench cell's mode (repeatable) -- compare "
                         "mixed layouts against the pure-mode tables")
    args = ap.parse_args(argv)
    from repro.launch.cli import init_compile_cache
    init_compile_cache()
    mode_overrides = ()
    if args.mode_override:
        from repro.core.strategy import parse_mode_override
        mode_overrides = tuple(parse_mode_override(s)
                               for s in args.mode_override)

    wl = (workloads.SMOKE_WORKLOADS if args.smoke
          else workloads.FULL_WORKLOADS)
    if args.axis:
        unknown = set(args.axis) - {w.name for w in wl}
        if unknown:
            ap.error(f"unknown axes {sorted(unknown)}; known: "
                     f"{[w.name for w in wl]}")
        wl = tuple(w for w in wl if w.name in args.axis)

    ctx = execution.RunContext(
        mode_overrides=mode_overrides, timed=args.timed,
        timing=execution.TimingSpec(warmup_steps=args.warmup_steps,
                                    timed_steps=args.timed_steps))
    RESULTS.mkdir(exist_ok=True)
    rd = results.RunDir.create(smoke=args.smoke, timed=args.timed)
    all_out = {}
    failures = 0
    for w in wl:
        t0 = time.time()
        try:
            doc = execution.run_workload(w, ctx)
            flat = RESULTS / w.flat if w.flat else None
            rd.write_axis(doc, flat_path=flat)
            all_out[w.name] = doc
            status = "ok"
        except Exception as e:
            traceback.print_exc()
            all_out[w.name] = {"error": str(e)}
            rd.record_failure(w.name, str(e))
            status = "FAILED"
            failures += 1
        print(f"# bench {w.name}: {status} ({time.time()-t0:.0f}s)")
    rd.finalize()
    out_name = "bench_smoke.json" if args.smoke else "bench_results.json"
    with open(RESULTS / out_name, "w") as f:
        json.dump(all_out, f, indent=2, default=float)
    print(f"# run dir: {rd.path}")
    print("name,us_per_call,derived")
    for name, us, derived in ctx.rows:
        print(f"{name},{us:.1f},{derived:.6g}")
    if args.smoke and failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

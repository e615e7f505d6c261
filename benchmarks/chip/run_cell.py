#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

  python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. The run
builds the program's own training run for it, with weights and traffic
from ``--seed``, warms every shape up (set-up), times a window of
``--seconds`` seconds of the launcher's loop, then compares the first
three steps with the plain float32 reference. With ``--trace 0`` the
result holds the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result holds its per-layer
metrics, the device's busy and window seconds, and a breakdown.

The last line of standard output is the result, one JSON object; the
numbers compared and their limits are the last lines of standard error.
Without a TPU, or with another number of chips than the cell asks for,
it exits non-zero before running anything.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# run as a script, this file's own directory would come first on the path,
# and its module trace.py would hide the standard library's
sys.path[0] = str(ROOT)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness
    cell = harness.load_cell(args.workload, ROOT)
    try:
        devices = harness.require_tpu(cell.chips)
    except harness.NoChip as e:
        log(f"run_cell: {e}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         devices, T_START, log=log)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

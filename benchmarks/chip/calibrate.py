#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 benchmarks/chip/calibrate.py --workload <cell> \
      --seeds 1,2,... --control-seeds 1,2,3 --faults half_batch \
      [--out calib.jsonl]

For every seed, in one process: the program's run of the cell (built,
driven through its first three steps exactly as a benchmark run does)
and the plain float32 reference over the same steps, then the three
numbers of ``correct.numbers``. On the control seeds it adds the
control, the reference computed with fp8 matmuls in the program's place;
for each fault named, the program's run with that fault planted
(``faults.py``). Each seed prints one JSON line; the last line is the
summary: per number, the largest reading of the sound runs (the lower
reading) and the smallest of the control and of each fault.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def program_readings(harness, correct, cell, seed, devices, fault=None):
    """The program's first three steps of one seed (optionally with a
    fault planted), read as a benchmark run reads them."""
    from benchmarks.chip import faults
    ctx = faults.FAULTS[fault] if fault else None
    if fault == "no_exchange":          # must be live while tracing
        with ctx():
            st = harness.build(cell, seed, devices)
            r = _three_steps(harness, correct, st, cell, seed)
    else:
        st = harness.build(cell, seed, devices)
        if ctx:
            with ctx(st):
                r = _three_steps(harness, correct, st, cell, seed)
        else:
            r = _three_steps(harness, correct, st, cell, seed)
    harness.free_state(st)
    return r


def _three_steps(harness, correct, st, cell, seed):
    losses = []
    for step in range(harness.WARMUP_STEPS):
        losses.append(harness.drive(st, step))
        if step == 0:
            g1 = harness.first_grad_norms(st)
    return correct.Readings(losses, g1, harness.change_norms(st, cell, seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from benchmarks.chip import harness
    cell = harness.load_cell(args.workload, ROOT)
    devices = harness.require_tpu(cell.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from benchmarks.chip import correct, reference
    harness.init_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    fault_names = [f for f in args.faults.split(",") if f]
    rows = []

    def emit(line: dict) -> None:
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")

    for seed in seeds:
        t0 = time.perf_counter()
        row = {"seed": seed,
               "program": program_readings(harness, correct, cell, seed,
                                           devices)}
        t1 = time.perf_counter()
        if seed in control:
            for f in fault_names:
                row[f] = program_readings(harness, correct, cell, seed,
                                          devices, f)
        t2 = time.perf_counter()
        ref = reference.train(cell.family, cell.config, cell.job, cell.mix,
                              seed, harness.WARMUP_STEPS, devices)
        t3 = time.perf_counter()
        if seed in control:
            c = reference.train(cell.family, cell.config, cell.job, cell.mix,
                                seed, harness.WARMUP_STEPS, devices,
                                precision="fp8")
            row["control"] = correct.Readings(c.losses, c.grad_norms[0],
                                              c.change_norms)
        t4 = time.perf_counter()
        line = {"seed": seed, "ref_losses": ref.losses,
                "seconds": {"program": t1 - t0, "faults": t2 - t1,
                            "reference": t3 - t2, "control": t4 - t3}}
        for k, r in row.items():
            if k != "seed":
                line[k] = {"numbers": correct.numbers(r, ref),
                           "losses": r.losses}
        rows.append(line)
        emit(line)
    summary = {"workload": args.workload, "seeds": seeds,
               "lower": {}, "upper": {}}
    for n in ("loss_gap", "grad_gap", "change_gap"):
        summary["lower"][n] = max(r["program"]["numbers"][n] for r in rows)
        for k in ["control"] + fault_names:
            got = [r[k]["numbers"][n] for r in rows if k in r]
            if got:
                summary["upper"].setdefault(k, {})[n] = min(got)
    emit({"summary": summary})
    log(f"calibrate: {time.perf_counter() - T_START:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())

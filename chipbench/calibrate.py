#!/usr/bin/env python3
"""Read the numbers that set a cell's limits, on the chip, in one process.

    python3 chipbench/calibrate.py --workload <name> --seeds 11,12,... \
        [--control-seeds 3] [--faults token_altered,ssm_state_unchanged] \
        [--fault-seeds 3] [--seconds 5] [--trace-seeds 0]

For each seed it runs the cell as the benchmark does (set-up, a window at
the cell's own load, the comparison with the plain reference) and prints
one ``CALIB`` line with the compared numbers and ``correct``.  On the first
``--control-seeds`` seeds it also puts the control (the reference one
precision below the configuration's) in the program's place and prints
its ``correct``, which has to come out false; on the first
``--fault-seeds`` seeds it runs the cell again with each fault of
``faults.py`` planted in the program.  The limits in the traffic files
were set from these readings (``PERF.md``).  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

CONTROL = {"bfloat16": "fp8"}


def _line(kind: str, cell, seed: int, res) -> str:
    return "CALIB " + json.dumps({
        "workload": cell.name, "seed": seed, "kind": kind,
        "correct": res.correct,
        "checks": {c.name: [c.value, c.limit] for c in res.checks},
        "failed": res.failed, "attempted": res.attempted,
        "metrics": res.metrics, "device": res.device,
        "breakdown": res.breakdown, "notes": res.notes}, default=str)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--trace-seeds", type=int, default=0,
                    help="run the last this many seeds with --trace 1")
    args = ap.parse_args(argv)

    from chipbench import harness, spec

    cell = spec.find_cell(args.workload)
    harness.chips(cell.chips)
    harness.enable_compile_cache()
    runner = spec.load_runner(cell.config["runner"])
    control = CONTROL[cell.config["model"]["dtype"]]
    seeds = [int(s) for s in args.seeds.split(",")]
    for k, seed in enumerate(seeds):
        res = runner.run(cell, seed=seed, seconds=args.seconds,
                         trace=k >= len(seeds) - args.trace_seeds,
                         controls=(control,) if k < args.control_seeds
                         else ())
        print(_line("program", cell, seed, res), flush=True)
        for name, ctl in res.controls.items():
            print(_line(f"control:{name}", cell, seed, ctl), flush=True)
    for fault in filter(None, args.faults.split(",")):
        for seed in seeds[:args.fault_seeds]:
            res = runner.run(cell, seed=seed, seconds=args.seconds,
                             trace=False, fault=fault)
            print(_line(f"fault:{fault}", cell, seed, res), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())

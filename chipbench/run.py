#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json``.  Set-up loads and warms every shape the cell
uses; the window then measures for ``--seconds``; once it has closed, what
the timed path produced is compared with the plain reference.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end to end with ``--trace 0``, per layer with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each compared number beside its limit, also printed as the last
lines of standard error.

Without an accelerator, with fewer chips than the cell asks for, or without
the program (``src/``) beside it, it exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from chipbench import harness, spec

    try:
        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        print(f"chipbench: the program is not beside the benchmark ({e})",
              file=sys.stderr)
        return 3
    cell = spec.find_cell(args.workload)
    try:
        harness.chips(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    print(f"chipbench: {cell.name} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace} cache {harness.enable_compile_cache()}",
          file=sys.stderr, flush=True)
    runner = spec.load_runner(cell.config["runner"])
    res = runner.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace))
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    for k, v in sorted(res.notes.items()):
        print(f"note {k}: {v}", file=sys.stderr)
    harness.print_checks(res)
    print(harness.result_line(res, units), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.exit(1)

#!/usr/bin/env python3
"""Read on the chip, at a cell's own size, what the comparison gives with
a fault planted in the timed path (``faults.py``).

    python3 bench/fault_readings.py --workload <cell> --fault <name> \\
        --seconds 3 --seeds 1 2 3

Each seed is one whole run of the cell -- set-up, a short window, the
comparison -- in this one process, with the fault in place.  Prints one
JSON line per seed with every compared number; a limit has to lie below
the reading of a fault that it is there to catch.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import faults, run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(faults.FAULTS))
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    run.enable_compile_cache()
    device = run.check_device(cell.chips)
    for seed in args.seeds:
        with faults.FAULTS[args.fault]():
            res = run.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), device=device)
        print(json.dumps({
            "workload": cell.name, "fault": args.fault, "seed": seed,
            "correct": res["correct"],
            **{k: c["value"] for k, c in res["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

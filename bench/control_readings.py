#!/usr/bin/env python3
"""Read the control of ``check.py`` on the chip, at a cell's own size.

    python3 bench/control_readings.py --workload <cell> --seeds 1 2 3

For each seed this makes the cell's inputs as a run of the cell does,
puts the reference in three bfloat16 passes (``control.py``) in the
program's place -- on the device, one batch of the plan's E elements at
a time -- and reads the numbers ``check.py`` compares on the rows a run
samples.  A limit has to lie below every reading printed here.  The
benchmark's own runs never run this.  Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402

#: Rows per call of the control on the device.
CHUNK = 4096


def readings(cell, seed: int, target=None) -> dict:
    import jax
    import numpy as np

    from bench import check, control, traffic

    program = run.load_program(cell.cfg["program"])
    system = run.compile_system(cell, target)
    kind = traffic.make(system, program, cell.cfg, cell.mix, seed)
    kind.make_inputs()
    fn = jax.jit(lambda sh, ins: program.outputs(control, sh, ins))
    ins_rows = {k: [] for k in program.ELEMENT_INPUTS}
    outs_rows = {q: [] for q in program.OUTPUTS}
    for batch in kind.batches():
        batch = {k: np.asarray(v) for k, v in batch.items()}
        rows = traffic.sample_rows(kind.sample_rng, 1, kind.E)
        # the whole batch, CHUNK rows per call so that the device holds it
        got = [fn(kind.shared, {k: v[lo:lo + CHUNK]
                                for k, v in batch.items()})
               for lo in range(0, kind.E, CHUNK)]
        for q in program.OUTPUTS:
            outs_rows[q].append(np.concatenate(
                [np.asarray(g[q]) for g in got])[rows])
        for k, v in batch.items():
            ins_rows[k].append(v[rows])
    ins, outs = traffic.ins_cat(ins_rows), traffic.ins_cat(outs_rows)
    checks = check.compare(program, kind.shared, ins, outs,
                           cell.cfg["limits"])
    return {"seed": seed, "rows": len(next(iter(ins.values()))),
            **{k: c["value"] for k, c in checks.items()}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    run.enable_compile_cache()
    run.check_device(cell.chips)
    for seed in args.seeds:
        print(json.dumps({"workload": cell.name,
                          **readings(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

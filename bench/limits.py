"""Readings that the correctness limit of a cell is set from, on the chip.

    python bench/limits.py --workload hpcg-48.cg --seeds 11,12,13 \\
        --seconds 5 --control-seeds 3

Set-up runs once; then, for each seed, one window at the cell's own load
and the comparison a run makes.  Each line gives the program's reading (the
worst componentwise error of what the window served) and, for the first
``--control-seeds`` seeds, the control's: the reference in bfloat16 put in
the program's place on the same requests.  The limit lies between the
largest program reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    from bench import chip, harness, reference

    cell = harness.resolve(harness.load_benchmark(), args.workload)
    chip.devices_or_exit(cell.chips, args.workload)
    drv = cell.driver.Driver(cell, harness.Spans())
    drv.setup()
    try:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            drv.prepare(seed, args.seconds)
            run = harness.Run(cell=cell, seed=seed, seconds=args.seconds)
            run.t0 = time.perf_counter()
            drv.measure(args.seconds, run)
            run.t1 = time.perf_counter()
            answers = drv.collect(run)
            line = {"seed": seed, "answers": len(answers), "attempted": run.attempted,
                    "failed": run.failed, "program": harness.worst_error(answers)}
            if i < args.control_seeds:
                line["control"] = harness.worst_error(answers, reference.control_spmv)
            print(json.dumps(line), flush=True)
    finally:
        drv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

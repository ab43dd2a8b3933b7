"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are resolved from
``BENCHMARK.json`` (see ``bench/harness.py``).  Set-up (data from the seed,
plan builds, compiles, warm-up) runs first and is timed as ``setup_s``; then
the traffic runs for ``--seconds``; then what the window served is compared
with the float64 reference.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` profiles the window and reports its per-layer metrics.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, [``breakdown``],
``checks``); the last lines of standard error are the numbers compared, each
beside its limit.  Without a TPU, or with fewer chips than the cell asks
for, the command exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import chip, harness

    cell = harness.resolve(harness.load_benchmark(), args.workload)
    chip.devices_or_exit(cell.chips, args.workload)
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

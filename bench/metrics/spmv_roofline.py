"""Share of the HBM roofline that one served SpMV reaches, in %.

Bytes are the matrix's own least traffic, ``nnz·(4+4) + n_rows·4 +
n_cols·4`` (float32 values, int32 column indices, x read once, y written
once), whatever layout the program packs.  Time is the device time of every
program the server ran in the window (pack gather, kernel, combine: all
modules except the benchmark's own ``bench_*``), per SpMV.  SpMV does 2
operations per 8 bytes, so the bandwidth bound is the roofline."""
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def served(module_name: str) -> bool:
    return "bench_" not in module_name


def read(run):
    if run.trace is None or not run.iterations:
        return None
    seconds = run.trace.module_seconds(served) / run.iterations
    if seconds <= 0:
        return None
    with open(PEAKS) as f:
        peaks = json.load(f)
    kind = run.counters["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    c = run.counters
    nbytes = c["nnz"] * 8 + c["n_rows"] * 4 + c["n_cols"] * 4
    return 100.0 * nbytes / peaks[kind]["hbm_bytes_per_s"] / seconds

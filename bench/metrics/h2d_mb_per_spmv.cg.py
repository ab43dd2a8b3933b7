"""Bytes the serve path copied from host to device per SpMV in the window,
in MB of 10^6 bytes (the program's counter ``serve.h2d_bytes``)."""
from bench import program_spans


def read(run):
    n = program_spans.count_per_spmv(run, "serve.h2d_bytes")
    return None if n is None else n / 1e6

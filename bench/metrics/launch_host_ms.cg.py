"""Host time from a resolved plan to the served program's dispatch per SpMV
in the window, in ms: bucket choice, ``vals_digest``, the compile-cache
get, operand staging and copies, the jit call (the program's span
``serve.launch``)."""
from bench import program_spans


def read(run):
    return program_spans.span_ms_per_spmv(run, "serve.launch")

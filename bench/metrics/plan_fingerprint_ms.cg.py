"""Host time of ``graph_fingerprint`` per SpMV in the window, in ms (the
program's span ``plan.fingerprint``, every caller)."""
from bench import program_spans


def read(run):
    return program_spans.span_ms_per_spmv(run, "plan.fingerprint")

"""Host time of ``affinity_graph_from_coo`` in plan resolution per SpMV in
the window, in ms (the program's span ``plan.affinity``)."""
from bench import program_spans


def read(run):
    return program_spans.span_ms_per_spmv(run, "plan.affinity")

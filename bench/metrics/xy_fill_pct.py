"""Share of the x and y tile slots that the window's launches walked that
held a distinct entry their cluster loads, in %: 100 · Σ ``serve.xy_loads``
/ Σ ``serve.xy_slots`` (the program's counters; the loads are the paper's
``n_touched + C``, the slots the plan's or bucket's ``k · (X_max + Y_max)``)."""
from bench import program_spans


def read(run):
    loads = program_spans.count_per_spmv(run, "serve.xy_loads")
    slots = program_spans.count_per_spmv(run, "serve.xy_slots")
    return None if loads is None or not slots else 100.0 * loads / slots

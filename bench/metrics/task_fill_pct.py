"""Share of the task slots that the window's launches walked that held a
task, in %: 100 · Σ ``serve.tasks`` / Σ ``serve.task_slots`` (the
program's counters; the slots are the plan's ``k · E_max``, or the
bucket's)."""
from bench import program_spans


def read(run):
    tasks = program_spans.count_per_spmv(run, "serve.tasks")
    slots = program_spans.count_per_spmv(run, "serve.task_slots")
    return None if tasks is None or not slots else 100.0 * tasks / slots

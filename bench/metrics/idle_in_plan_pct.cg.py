"""Share of the traced window in which the device was idle while the host
resolved a plan, in %: the program's ``plan.resolve`` spans, mapped onto
the trace's clock, intersected with the first busy chip's idle time."""
from bench import program_spans


def read(run):
    ns = program_spans.idle_inside(run, "plan.resolve")
    return None if ns is None else 100.0 * ns / (run.trace.window_s * 1e9)

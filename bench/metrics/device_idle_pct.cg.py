"""Share of the traced window in which no op ran on the device, in %."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share

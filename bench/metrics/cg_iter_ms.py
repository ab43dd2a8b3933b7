"""The whole window over the CG iterations completed in it, in ms."""


def read(run):
    if not run.iterations:
        return None
    return run.window_s * 1e3 / run.iterations

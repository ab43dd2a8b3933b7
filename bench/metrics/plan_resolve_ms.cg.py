"""Mean host time of ``GraphServer._plan_for`` per SpMV in the window, in
ms (benchmark span ``plan_for``)."""
import numpy as np


def read(run):
    d = run.spans.durations("plan_for", run.t0, run.t1)
    return float(np.mean(d)) * 1e3 if d else None

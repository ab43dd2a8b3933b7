"""Host partition + pack of the configuration's matrix in set-up, in s:
``ServeInfo.partition_time_s`` of the first request (the program's own
``ServicePlan.compute_time_s``)."""


def read(run):
    return run.counters.get("plan_build_s")

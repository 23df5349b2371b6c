"""Host seconds the partitioner spent building the step's plan (trace,
propagate, lower, optimize, verify): ``_CacheEntry.build_s`` of the runner's
one plan."""


def read(rec):
    return rec.get("plan_build_s")

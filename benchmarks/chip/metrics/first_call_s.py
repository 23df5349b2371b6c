"""Host seconds of the runner's first call with concrete arrays: lowering
the partitioned step, its XLA compile (or compile-cache load) and the
enqueue, the part of set-up after the plan build.  The program counts it
(``_CacheEntry.first_call_s``); the record does not carry it, so it is
read from the process-level plan cache, which holds the cell's one plan.
Nothing to read where the program has no such counter."""


def read(rec):
    try:
        from repro.core.partitioner import process_plan_cache_entries
    except ImportError:
        return None
    seen = [getattr(e, "first_call_s", None)
            for e in process_plan_cache_entries()]
    seen = [s for s in seen if s is not None]
    return seen[0] if len(seen) == 1 else None

"""Megabytes (1e6 bytes) one device receives per step from the operand
gathers of the partitioner's fallback, as the plan was lowered, scan bodies
at their trip count: ``PlanStats.fallback_bytes`` of the runner's one plan.
The record does not carry it, so it is read from the process-level plan
cache, as ``first_call_s`` is.  Nothing to read where the program has no
such counter."""


def read(rec):
    try:
        from repro.core.partitioner import process_plan_cache_entries
    except ImportError:
        return None
    seen = [getattr(e.plan.stats, "fallback_bytes", None)
            for e in process_plan_cache_entries()]
    seen = [s for s in seen if s is not None]
    return seen[0] / 1e6 if len(seen) == 1 else None

"""Calls per step of the flash forward kernel (the shipped
``_flash_attention_kernel``): ``PlanStats.kernel_calls`` of the runner's
one plan, scan bodies at their trip count.  One per layer where the
backward reads the residuals the forward saved; two where layer remat runs
the forward again to rebuild them.  The record does not carry it, so it is
read from the process-level plan cache, as ``fallback_gather_mb`` is.
Nothing to read where the program has no such counter."""

KERNEL = "_flash_attention_kernel"


def read(rec):
    try:
        from repro.core.partitioner import process_plan_cache_entries
    except ImportError:
        return None
    seen = [getattr(e.plan.stats, "kernel_calls", None)
            for e in process_plan_cache_entries()]
    seen = [s for s in seen if s is not None]
    return float(seen[0].get(KERNEL, 0)) if len(seen) == 1 else None

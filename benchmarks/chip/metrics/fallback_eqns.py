"""Equations of the step that the partitioner lowered by its gather-
everything fallback: the sum of ``PlanStats.fallbacks``."""


def read(rec):
    fb = rec.get("fallbacks")
    return None if fb is None else float(sum(fb.values()))

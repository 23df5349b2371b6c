"""Percent of the traced window in which no operation ran on a device,
averaged over the cell's devices."""


def read(rec):
    t = rec.get("trace")
    return None if not t else 100.0 * t["idle_share"]

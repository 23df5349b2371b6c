"""Milliseconds per step in which a device ran a collective and nothing
else, from the device trace, averaged over the devices.  Nothing to read
where the trace holds no collective operation (one chip)."""


def read(rec):
    t = rec.get("trace")
    if not t or not t["collective_ops"] or not rec.get("steps_traced"):
        return None
    return t["exposed_collective_s"] * 1e3 / rec["steps_traced"]

"""Device idle per window step caused by the input layer, in milliseconds:
the time in which no operation ran on a device while the host was inside
the harness's ``bench.batch`` span (``TokenPipeline.batch_at``, its sync
with the device, and the batch's transfer), from the device trace, averaged
over the devices.  A faster model step leaves it as it is; an input layer
that neither syncs nor stalls reads near 0."""


def read(rec):
    t = rec.get("trace")
    if not t or not rec.get("steps_traced"):
        return None
    return t["idle_in_span_s"]["bench.batch"] * 1e3 / rec["steps_traced"]
